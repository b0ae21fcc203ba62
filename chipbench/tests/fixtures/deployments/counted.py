"""A test deployment: the paper's, whose reference also replays the
``counts`` ops that the ``counted_burst`` driver records after each step,
comparing the program's completed and lost instances with its own there."""
from pathlib import Path

from harness import load_module

paper = load_module(Path(__file__).with_name("paper.py"))
build, warm_fleet, warm_kernels = paper.build, paper.warm_fleet, paper.warm_kernels


class Reference(paper.Reference):
    count_gap = 0

    def replay_counts(self, completed: int, lost: int) -> None:
        gap = abs(completed - self.completed) + abs(lost - self.lost)
        self.count_gap = max(self.count_gap, gap)

    def checks(self):
        return {"count_gap": self.count_gap}


def reference(setup) -> Reference:
    return Reference.for_setup(setup)
