"""Jaxpr-level auditing of the repo's registered jitted kernels.

AST lint sees call syntax; it cannot see what a kernel *traces to*.  This
module abstract-traces each registered kernel with ``jax.make_jaxpr`` over
shape/dtype specs derived from the fleet-snapshot layout at several fleet
sizes, and checks the lowered program against the contracts the rest of
the repo relies on:

  * **x64 bit-identity** (PR 2): the batched decision kernels run under
    ``jax.enable_x64`` and must be float64 end to end — a
    stray ``float32`` constant or low-precision promotion silently breaks
    batched==scalar parity.  Any non-f64 floating value in the jaxpr of an
    ``x64=True`` kernel is flagged.
  * **no host round-trips**: ``pure_callback``/``io_callback``/
    ``debug_callback``/``debug_print`` (and in/outfeed) primitives in a
    hot kernel stall the dispatch queue; the audit walks every sub-jaxpr
    (pjit, scan, cond bodies) looking for them.
  * **bounded recompilation**: ``decide_batch`` pads wave sizes to a
    bounded shape set (:func:`repro.core.batched._padded`), so a sweep of
    wave sizes must produce exactly the padded-bucket count of distinct
    lowerings.  ``expected_lowerings`` pins that number; more means a
    missing pad or a ``static_argnums`` mistake is recompiling per wave.
  * **donation**: every buffer named by ``donate_argnums`` must be
    reusable — each donated input leaf needs a matching (shape, dtype)
    output leaf, otherwise the donation is silently dropped and the
    serving engine double-buffers its KV cache.

The audit runs from the ``kernel-hygiene`` lint rule's ``finalize``: the
registered repo kernels come from :func:`builtin_targets`; test fixtures
self-describe by exporting a module-level ``AUDIT_TARGETS`` list of
:class:`KernelSpec` (the rule spots the assignment in the AST and imports
the module by path).
"""
from __future__ import annotations

from collections import Counter
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "KernelSpec",
    "audit_spec",
    "builtin_targets",
    "f64",
    "f32",
    "i64",
    "i32",
    "bools",
]


# -- shape-spec helpers (ShapeDtypeStructs without importing jax at top) -------

def _sds(shape: Tuple[int, ...], dtype: str):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def f64(*shape: int):
    return _sds(shape, "float64")


def f32(*shape: int):
    return _sds(shape, "float32")


def i64(*shape: int):
    return _sds(shape, "int64")


def i32(*shape: int):
    return _sds(shape, "int32")


def bools(*shape: int):
    return _sds(shape, "bool")


@dataclass
class KernelSpec:
    """One kernel to audit.

    ``fn`` is a thunk (imports stay lazy so the linter never pays for jax
    unless the rule actually runs); ``build(point)`` turns one sweep point
    (e.g. ``{"D": 6, "B": 100}``) into the positional arguments —
    ``ShapeDtypeStruct`` pytrees for traced args, plain Python values for
    scalars and for ``static_argnums`` positions.
    """

    name: str
    fn: Callable[[], Callable]
    build: Callable[[Dict[str, int]], Tuple[Any, ...]]
    sweep: Tuple[Dict[str, int], ...]
    x64: bool = False
    static_argnums: Tuple[int, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    expected_lowerings: Optional[int] = None
    anchor: Optional[str] = None      # substring locating the finding's line


# -- jaxpr walking -------------------------------------------------------------

_HOST_PRIMITIVES = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "callback",
    "debug_print",
    "infeed",
    "outfeed",
}


def _subjaxprs(value: Any):
    """Yield raw Jaxprs nested inside an eqn param value."""
    from jax.extend import core as jcore

    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _subjaxprs(v)


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _walk_eqns(sub)


def _aval_of(var) -> Optional[Any]:
    return getattr(var, "aval", None)


def _bad_float(dtype) -> bool:
    import numpy as np

    return (
        np.issubdtype(dtype, np.floating)
        and np.dtype(dtype) != np.dtype("float64")
    )


def _scan_x64(closed, name: str) -> List[str]:
    """Non-f64 floating values inside a bit-identical x64 kernel."""
    problems: List[str] = []
    seen = set()

    def flag(what: str, dtype) -> None:
        msg = (
            f"x64 kernel `{name}` carries a {dtype} {what} — the batched "
            "twins are bit-identical float64 end to end (PR 2); promote "
            "the constant/op to float64"
        )
        if msg not in seen:
            seen.add(msg)
            problems.append(msg)

    for const in closed.consts:
        dtype = getattr(const, "dtype", None)
        if dtype is not None and _bad_float(dtype):
            flag("constant", dtype)
    for eqn in _walk_eqns(closed.jaxpr):
        for var in eqn.invars:
            aval = _aval_of(var)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            if _bad_float(aval.dtype):
                what = (
                    "literal" if type(var).__name__ == "Literal"
                    else f"`{eqn.primitive.name}` input"
                )
                flag(what, aval.dtype)
        for var in eqn.outvars:
            aval = _aval_of(var)
            if aval is not None and hasattr(aval, "dtype") \
                    and _bad_float(aval.dtype):
                flag(f"`{eqn.primitive.name}` output", aval.dtype)
    return problems


def _scan_callbacks(closed, name: str) -> List[str]:
    hits = sorted({
        eqn.primitive.name
        for eqn in _walk_eqns(closed.jaxpr)
        if eqn.primitive.name in _HOST_PRIMITIVES
    })
    return [
        f"kernel `{name}` lowers a host-callback primitive `{p}` — "
        "debug prints / callbacks stall the dispatch queue; strip them "
        "from the registered kernel"
        for p in hits
    ]


def _leaf_avals(tree) -> List[Tuple[Tuple[int, ...], str]]:
    import jax

    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", ""))
        out.append((shape, dtype))
    return out


def _check_donation(spec: KernelSpec, args: Tuple[Any, ...],
                    closed) -> List[str]:
    """Every donated input leaf must find a (shape, dtype)-matching output
    leaf, or XLA silently drops the donation."""
    problems = []
    outs = Counter(
        (tuple(a.shape), str(a.dtype))
        for a in closed.out_avals if hasattr(a, "shape")
    )
    for argnum in spec.donate_argnums:
        if argnum >= len(args):
            problems.append(
                f"kernel `{spec.name}` donates argnum {argnum} but only "
                f"{len(args)} arguments were specified"
            )
            continue
        for shape, dtype in _leaf_avals(args[argnum]):
            if outs[(shape, dtype)] > 0:
                outs[(shape, dtype)] -= 1
            else:
                problems.append(
                    f"kernel `{spec.name}` donates argnum {argnum} but its "
                    f"{dtype}{list(shape)} buffer has no matching output — "
                    "the donation is silently dropped and the buffer is "
                    "double-allocated"
                )
    return problems


def audit_spec(spec: KernelSpec) -> List[str]:
    """Run every check on one kernel; returns human-readable problems."""
    import jax

    try:
        fn = spec.fn()
    except Exception as e:  # the kernel itself failed to load
        return [f"kernel `{spec.name}` could not be loaded: {e!r}"]

    problems: List[str] = []
    lowerings: Dict[str, Dict[str, int]] = {}
    traced: List[Tuple[Dict[str, int], Tuple[Any, ...], Any]] = []
    for point in spec.sweep:
        args = spec.build(point)
        try:
            with jax.enable_x64(spec.x64):
                closed = jax.make_jaxpr(
                    fn, static_argnums=spec.static_argnums
                )(*args)
        except Exception as e:
            problems.append(
                f"kernel `{spec.name}` failed to trace at {point}: "
                f"{type(e).__name__}: {e}"
            )
            continue
        traced.append((point, args, closed))
        lowerings.setdefault(str(closed), point)

    if spec.expected_lowerings is not None and traced:
        n = len(lowerings)
        if n > spec.expected_lowerings:
            pts = ", ".join(str(p) for p in lowerings.values())
            problems.append(
                f"kernel `{spec.name}` lowers {n} distinct programs across "
                f"the size sweep (expected <= {spec.expected_lowerings}; "
                f"one per padded bucket) — wave sizes are recompiling; pad "
                f"the row count (`_padded`) or fix static_argnums "
                f"[distinct at: {pts}]"
            )

    seen = set()
    for i, (point, args, closed) in enumerate(traced):
        msgs: List[str] = []
        if spec.x64:
            msgs.extend(_scan_x64(closed, spec.name))
        msgs.extend(_scan_callbacks(closed, spec.name))
        if i == 0 and spec.donate_argnums:
            msgs.extend(_check_donation(spec, args, closed))
        for m in msgs:
            if m not in seen:
                seen.add(m)
                problems.append(m)
    return problems


# -- the registered repo kernels ----------------------------------------------

_IBDASH_GAMMA = 3          # replication budget used for the trace specs
_ALPHA, _BETA = 0.5, 0.25


def _batched_kernel(key: str) -> Callable[[], Callable]:
    def thunk():
        from repro.core import batched

        return batched._jax()[key]

    return thunk


def _padded(B: int) -> int:
    from repro.core import batched

    return batched._padded(B)


# Fleet-size sweep: wave sizes B spanning three padded buckets (8 -> 8,
# 100 -> 128, 900/1000 -> 1024) at two fleet sizes D.  The ibdash scan's
# shapes depend only on n_scan = min(gamma+1, D-1), which saturates for
# D >= gamma+2 — the audit *proves* fleet growth does not recompile it.
_FLEET_SWEEP = (
    {"D": 6, "B": 8},
    {"D": 6, "B": 100},
    {"D": 24, "B": 900},
    {"D": 24, "B": 1000},
)


def _ibdash_args(p):
    B = _padded(p["B"])
    n_scan = min(_IBDASH_GAMMA + 1, p["D"] - 1)
    return (
        f64(B, n_scan + 1),              # s_total
        f64(B, n_scan + 1),              # s_pf
        i64(B),                          # n_feas
        _ALPHA, _BETA, _IBDASH_GAMMA,
    )


def _lavea_args(p):
    B = _padded(p["B"])
    return (f64(B, p["D"]), bools(B, p["D"]))


def _round_robin_args(p):
    B = _padded(p["B"])
    return (bools(B, p["D"]), i64(B))


def _tier_args(p):
    B = _padded(p["B"])
    return (f64(B, p["D"]), bools(B, p["D"]), i64(p["D"]), 2.5, 3)


def _ops_kernel(opname: str, **fixed) -> Callable[[], Callable]:
    def thunk():
        from repro.kernels import ops

        op = getattr(ops, opname)

        def wrapped(*arrays):
            return op(*arrays, impl="ref", **fixed)

        return wrapped

    return thunk


_ENGINE_CTX: Dict[str, Any] = {}


def _engine_ctx() -> Dict[str, Any]:
    """Tiny LM mirroring tests/test_serve.py, built once: abstract param
    avals via eval_shape, concrete (tiny) caches mapped to avals."""
    if _ENGINE_CTX:
        return _ENGINE_CTX
    import jax

    from repro.configs import get_config
    from repro.models import LM, reduced

    cfg = reduced(get_config("qwen1.5-0.5b"), n_layers=2, vocab=128)
    model = LM(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B, S = 2, 32
    caches = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        model.init_cache(B, S),
    )
    _ENGINE_CTX.update(
        model=model, params=params, caches=caches, B=B, S=S,
        vocab=cfg.vocab,
    )
    return _ENGINE_CTX


def _engine_decode() -> Callable:
    import jax

    ctx = _engine_ctx()
    # mirrors ServingEngine.__init__: jax.jit(model.decode_step,
    # donate_argnums=(3,))
    return jax.jit(ctx["model"].decode_step, donate_argnums=(3,))


def _engine_prefill() -> Callable:
    import jax

    ctx = _engine_ctx()
    return jax.jit(ctx["model"].prefill, donate_argnums=(2,))


def _engine_decode_args(p):
    ctx = _engine_ctx()
    B = ctx["B"]
    return (ctx["params"], i32(B), i32(B), ctx["caches"])


def _engine_prefill_args(p):
    ctx = _engine_ctx()
    return (ctx["params"], {"tokens": i32(ctx["B"], 8)}, ctx["caches"])


def builtin_targets() -> Dict[str, List[KernelSpec]]:
    """Registered kernels keyed by the repo-relative file that defines
    them; the rule audits an entry when its file is in the scanned set."""
    return {
        "src/repro/core/batched.py": [
            KernelSpec(
                name="ibdash_scan_kernel",
                fn=_batched_kernel("ibdash_scan_kernel"),
                build=_ibdash_args, sweep=_FLEET_SWEEP, x64=True,
                expected_lowerings=3,
                anchor="def ibdash_scan_kernel",
            ),
            KernelSpec(
                name="lavea_kernel",
                fn=_batched_kernel("lavea_kernel"),
                build=_lavea_args, sweep=_FLEET_SWEEP, x64=True,
                expected_lowerings=3,
                anchor="def lavea_kernel",
            ),
            KernelSpec(
                name="round_robin_kernel",
                fn=_batched_kernel("round_robin_kernel"),
                build=_round_robin_args, sweep=_FLEET_SWEEP, x64=True,
                expected_lowerings=3,
                anchor="def round_robin_kernel",
            ),
            KernelSpec(
                name="tier_escalation_kernel",
                fn=_batched_kernel("tier_escalation_kernel"),
                build=_tier_args, sweep=_FLEET_SWEEP, x64=True,
                static_argnums=(4,),
                expected_lowerings=3,
                anchor="def tier_escalation_kernel",
            ),
        ],
        "src/repro/kernels/ops.py": [
            KernelSpec(
                name="attention",
                fn=_ops_kernel("attention", causal=True),
                build=lambda p: (
                    f32(1, p["S"], 2, 8), f32(1, p["S"], 2, 8),
                    f32(1, p["S"], 2, 8),
                ),
                sweep=({"S": 16}, {"S": 32}),
                expected_lowerings=2,
                anchor="def attention",
            ),
            KernelSpec(
                name="decode_attention",
                fn=_ops_kernel("decode_attention"),
                build=lambda p: (
                    f32(1, 2, 8), f32(1, p["S"], 2, 8),
                    f32(1, p["S"], 2, 8), i32(1),
                ),
                sweep=({"S": 16}, {"S": 32}),
                expected_lowerings=2,
                anchor="def decode_attention",
            ),
            KernelSpec(
                name="rwkv6",
                fn=_ops_kernel("rwkv6"),
                build=lambda p: (
                    f32(1, p["T"], 2, 8), f32(1, p["T"], 2, 8),
                    f32(1, p["T"], 2, 8), f32(1, p["T"], 2, 8),
                    f32(2, 8), f32(1, 2, 8, 8),
                ),
                sweep=({"T": 8}, {"T": 16}),
                expected_lowerings=2,
                anchor="def rwkv6",
            ),
        ],
        "src/repro/serve/engine.py": [
            KernelSpec(
                name="engine.decode_step",
                fn=_engine_decode,
                build=_engine_decode_args, sweep=({},),
                donate_argnums=(3,),
                expected_lowerings=1,
                anchor="jax.jit(model.decode_step",
            ),
            KernelSpec(
                name="engine.prefill",
                fn=_engine_prefill,
                build=_engine_prefill_args, sweep=({},),
                donate_argnums=(2,),
                expected_lowerings=1,
                anchor="jax.jit(model.prefill",
            ),
        ],
    }
