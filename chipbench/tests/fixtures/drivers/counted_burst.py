"""A test driver: the burst loop, which also records a ``counts`` op with
the program's completed and lost instances after each ``step`` op."""
from pathlib import Path

from harness import load_module

burst = load_module(Path(__file__).with_name("burst.py"))


class _Schedule(list):
    def __init__(self, orch):
        super().__init__()
        self.orch = orch

    def append(self, op) -> None:
        super().append(op)
        if op[0] == "step":
            stats = self.orch.stats
            super().append(("counts", stats.completed, stats.lost))


class Driver(burst.Driver):
    def window(self) -> None:
        self.run.schedule = _Schedule(self.s.orch)
        super().window()
