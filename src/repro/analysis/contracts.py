"""IR contract checks: lower the registered hot paths and statically prove
the engine's invariants on the jaxpr/HLO.

The contracts (ids ``IRC00x``; the lint layer owns ``REPROxxx``):

``IRC001`` zero collectives — ``distributed.update_step`` (the post-pivot
    O(n/p) maintenance axpy) must lower with NO collective ops at all.
    This is PR 2's design point; before this gate it was only demonstrated
    by a one-off dry-run.
``IRC002`` dense-pass discipline — the reduced costs are MAINTAINED, so
    ``pq_step`` performs exactly ONE top-level dense O(m·n/p) sweep of A
    (the pricing matvec; the dense flip-absorption fallback may add one
    more inside a ``cond`` branch) and ``update_step`` performs none.
    ``refresh_step`` is the only full-recompute site (``d = c - Aᵀy`` +
    the basic-value rebuild: one or two dense passes, recorded).
``IRC003`` no host round-trips in device loops — no python-callback
    custom-calls, infeed/outfeed or send/recv inside a ``while`` body
    (jaxpr level: no callback primitives anywhere in the hot path).
``IRC004`` collective budget — per-pivot collective bytes of ``pq_step``
    within the declared O(num_buckets + p·K + m) budget
    (:func:`pq_collective_budget`), via ``hlo_analysis.collective_bytes``.
``IRC005`` dtype preservation — lowering a hot path with f32 inputs must
    not introduce any f64 intermediate (under the repo's x64-enabled
    process a stray Python-int ``arange``/division silently promotes).

Every check reports through :class:`repro.analysis.report.Violation` with
``path`` = ``<hot path>@<mesh>`` so the baseline ratchet addresses hot
paths exactly like lint addresses files.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend import core as jcore
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.report import Violation
from repro.distributed import hlo_analysis

# the analysis layer deliberately lowers every hot path at f64 (the x64
# production dtype) AND at f32 to prove dtype preservation; this is the
# probe dtype, not engine math:
_F64 = jnp.float64  # repro: allow[REPRO002] analysis-layer probe dtype

CONTRACTS: Dict[str, str] = {
    "IRC001": "zero collectives in the post-pivot update step",
    "IRC002": "dense-pass discipline (maintained reduced costs: one "
              "pricing sweep, refresh is the only recompute site)",
    "IRC003": "no host callbacks/transfers inside device while loops",
    "IRC004": "per-pivot collective bytes within the declared budget",
    "IRC005": "dtype preservation (no silent f64 introduction)",
}

# headroom over the analytic byte model: XLA pads bools, fuses scalar
# collectives and may tuple-combine gathers — 4x absorbs layout variance
# while still catching an accidental O(n) collective (which is orders of
# magnitude over budget, not a constant factor).
BUDGET_HEADROOM = 4.0

_COLLECTIVE_PRIMS = ("psum", "pmin", "pmax", "pargmin", "pargmax",
                     "all_gather", "all_to_all", "ppermute",
                     "reduce_scatter", "pbroadcast")
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "outside_call", "host_callback")


def pq_collective_budget(p: int, m: int, num_buckets: int = 128,
                         gather_k: int = 128, dtype_bytes: int = 8) -> float:
    """Declared per-pivot collective-byte budget for ``pq_step``.

    Mirrors the step's design-point traffic, O(num_buckets + p·K + m):
    the BFRT histogram all-reduce, the (p, K) exact-walk candidate
    all-gathers (3 float + 2 bool + 1 int64 per candidate, plus the
    per-shard trunc/kth scalars), the fvec/Acol psums and a fixed scalar
    overhead — times :data:`BUDGET_HEADROOM`.  Anything O(n) blows this
    budget by construction.
    """
    hist = 2 * num_buckets * dtype_bytes               # all-reduce
    gathered = p * gather_k * (3 * dtype_bytes + 2 + 8)
    shard_scalars = p * (1 + dtype_bytes)              # trunc + kth
    vecs = 2 * 2 * m * dtype_bytes                     # fvec + Acol psums
    misc = 64 * dtype_bytes                            # rmin/rmax/n_flips/...
    return BUDGET_HEADROOM * (hist + gathered + shard_scalars + vecs + misc)


# ------------------------------------------------------------ jaxpr walking


def _sub_jaxprs(v):
    if isinstance(v, jcore.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jcore.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def walk_eqns(jaxpr, visit: Callable, ctx: Tuple[str, ...] = ()) -> None:
    """Visit every eqn of ``jaxpr`` and its nested sub-jaxprs (while
    bodies, cond branches, scan/pjit/shard_map/pallas inner jaxprs).
    ``ctx`` is the tuple of enclosing structured-control primitives."""
    for eqn in jaxpr.eqns:
        visit(eqn, ctx)
        name = eqn.primitive.name
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                walk_eqns(sub, visit, ctx + (name,))


def _jaxpr_of(fn, *args) -> jcore.Jaxpr:
    return jax.make_jaxpr(fn)(*args).jaxpr


def collective_prims(jaxpr) -> List[Tuple[str, Tuple[str, ...]]]:
    found: List[Tuple[str, Tuple[str, ...]]] = []

    def visit(eqn, ctx):
        # shard_map(check_vma=True) traces psum as ``psum_invariant``
        name = eqn.primitive.name.removesuffix("_invariant")
        if name in _COLLECTIVE_PRIMS:
            found.append((eqn.primitive.name, ctx))

    walk_eqns(jaxpr, visit)
    return found


def callback_prims(jaxpr) -> List[Tuple[str, Tuple[str, ...]]]:
    found: List[Tuple[str, Tuple[str, ...]]] = []

    def visit(eqn, ctx):
        name = eqn.primitive.name
        if any(c in name for c in _CALLBACK_PRIMS):
            found.append((name, ctx))

    walk_eqns(jaxpr, visit)
    return found


def dense_dot_counts(jaxpr, threshold_elems: int) -> Tuple[int, int]:
    """(top_level, in_cond_branch) counts of dot_general eqns with an
    operand of at least ``threshold_elems`` elements — the "dense pass
    over A" detector behind IRC002."""
    top = cond = 0

    def visit(eqn, ctx):
        nonlocal top, cond
        if eqn.primitive.name != "dot_general":
            return
        size = 0
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            if shape:
                size = max(size, int(np.prod(shape)))
        if size >= threshold_elems:
            if "cond" in ctx:
                cond += 1
            else:
                top += 1

    walk_eqns(jaxpr, visit)
    return top, cond


def f64_introductions(jaxpr) -> List[str]:
    """Primitives whose outputs are float64 — meaningful only when the
    hot path was traced with float32 inputs (IRC005)."""
    found: List[str] = []

    def visit(eqn, ctx):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            # weak-typed f64 scalars (bare Python literals in a where/
            # select) never force promotion — only strong f64 counts
            if dt is not None and dt == _F64 and \
                    not getattr(aval, "weak_type", False):
                found.append(eqn.primitive.name)
                return

    walk_eqns(jaxpr, visit)
    return found


# ----------------------------------------------------------- hot-path audit


@dataclasses.dataclass
class HotPathResult:
    name: str          # e.g. "distributed.pq_step@2x2"
    wall_s: float
    record: dict       # collective bytes/counts, budgets, dense counts ...
    violations: List[Violation]

    @property
    def ok(self) -> bool:
        return not self.violations


def _mesh_label(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def _mesh_p(mesh) -> int:
    axes = [a for a in ("pod", "data", "model") if a in mesh.shape]
    return int(np.prod([mesh.shape[a] for a in axes]))


def _hlo_host_violations(name: str, hlo: str) -> List[Violation]:
    out = []
    for h in hlo_analysis.host_transfer_ops(hlo):
        if h["in_while"]:
            out.append(Violation(
                "IRC003", name, 0,
                f"host op {h['op']}({h['target']}) inside while body "
                f"{h['computation']} (x{h['trips']} trips)"))
    return out


def _callback_violations(name: str, jaxpr) -> List[Violation]:
    out = []
    for prim, ctx in callback_prims(jaxpr):
        if "while" in ctx:
            out.append(Violation("IRC003", name, 0,
                                 f"callback primitive {prim} inside "
                                 f"while body (ctx={'/'.join(ctx)})"))
    return out


def check_pq_step(mesh, m: int = 8, n: int = 1 << 14,
                  num_buckets: int = 128, gather_k: int = 128
                  ) -> HotPathResult:
    """pq_step: one dense pricing sweep (IRC002), collective bytes within
    the declared per-pivot budget (IRC004), no host loops (IRC003), no
    f64 on f32 inputs (IRC005)."""
    from repro.core.distributed import make_pq_step, pq_input_specs
    t0 = time.time()
    label = _mesh_label(mesh)
    name = f"distributed.pq_step@{label}"
    p = _mesh_p(mesh)
    viol: List[Violation] = []
    step, col_spec, vec_spec = make_pq_step(mesh, m, n,
                                            num_buckets=num_buckets,
                                            gather_k=gather_k)
    rep = P()
    in_sh = (NamedSharding(mesh, col_spec),) + tuple(
        NamedSharding(mesh, vec_spec) for _ in range(4)) + tuple(
        NamedSharding(mesh, rep) for _ in range(3))
    with mesh:
        compiled = jax.jit(step, in_shardings=in_sh).lower(
            *pq_input_specs(m, n)).compile()
        hlo = compiled.as_text()
        jaxpr = _jaxpr_of(step, *pq_input_specs(m, n))
        jaxpr32 = _jaxpr_of(step, *pq_input_specs(m, n,
                                                  dtype=jnp.float32))
    coll = hlo_analysis.collective_bytes(hlo, default_group=p)
    budget = pq_collective_budget(p, m, num_buckets, gather_k)
    if coll.total_bytes > budget:
        viol.append(Violation(
            "IRC004", name, 0,
            f"per-pivot collective bytes {coll.total_bytes:.3e} exceed "
            f"declared budget {budget:.3e} "
            f"(p={p}, NB={num_buckets}, K={gather_k})"))
    viol += _hlo_host_violations(name, hlo)
    viol += _callback_violations(name, jaxpr)
    top, in_cond = dense_dot_counts(jaxpr, m * (n // p))
    if top != 1:
        viol.append(Violation(
            "IRC002", name, 0,
            f"{top} top-level dense passes over A (expected exactly 1: "
            "the pricing sweep — reduced costs are maintained, no "
            "c - y@A recompute belongs here)"))
    if in_cond > 1:
        viol.append(Violation(
            "IRC002", name, 0,
            f"{in_cond} dense passes inside cond branches (expected <= 1:"
            " the flip-absorption dense fallback)"))
    f64s = f64_introductions(jaxpr32)
    if f64s:
        viol.append(Violation(
            "IRC005", name, 0,
            f"f32 inputs produce f64 intermediates via {sorted(set(f64s))}"
            ))
    rec = {"hot_path": name, "p": p, "m": m, "n": n,
           "collective_bytes": {k: float(v) for k, v in
                               coll.merged().items()},
           "collective_counts": dict(coll.count_by_kind),
           "budget_bytes": float(budget),
           "budget_used_frac": float(coll.total_bytes / budget),
           "dense_passes": {"top": top, "cond": in_cond}}
    return HotPathResult(name, time.time() - t0, rec, viol)


def check_update_step(mesh, m: int = 8, n: int = 1 << 14) -> HotPathResult:
    """update_step: ZERO collectives (IRC001) at both jaxpr and
    post-SPMD HLO level, zero dense passes (IRC002), f32-clean."""
    from repro.core.distributed import make_update_step
    t0 = time.time()
    label = _mesh_label(mesh)
    name = f"distributed.update_step@{label}"
    p = _mesh_p(mesh)
    viol: List[Violation] = []
    upd = make_update_step(mesh)
    axes = [a for a in ("pod", "data", "model") if a in mesh.shape]
    vec_spec = P(tuple(axes))
    rep = P()

    def abs_args(ft):
        f = lambda shape, dt=ft: jax.ShapeDtypeStruct(shape, dt)
        return (f((n,)), jax.ShapeDtypeStruct((n,), jnp.int32),
                f((n,)), jax.ShapeDtypeStruct((n,), jnp.bool_),
                f(()), jax.ShapeDtypeStruct((), jnp.int64),
                jax.ShapeDtypeStruct((), jnp.int64),
                jax.ShapeDtypeStruct((), jnp.bool_))

    in_sh = tuple(NamedSharding(mesh, vec_spec) for _ in range(4)) + \
        tuple(NamedSharding(mesh, rep) for _ in range(4))
    with mesh:
        compiled = jax.jit(upd, in_shardings=in_sh).lower(
            *abs_args(_F64)).compile()
        hlo = compiled.as_text()
        jaxpr = _jaxpr_of(upd, *abs_args(_F64))
        jaxpr32 = _jaxpr_of(upd, *abs_args(jnp.float32))
    coll = hlo_analysis.collective_bytes(hlo, default_group=p)
    n_coll = sum(coll.count_by_kind.values())
    if n_coll or coll.total_bytes:
        viol.append(Violation(
            "IRC001", name, 0,
            f"post-pivot update step lowered with {n_coll} collectives "
            f"({coll.total_bytes:.3e} bytes: "
            f"{sorted(coll.count_by_kind)}) — it must be purely "
            "shard-local"))
    jp_coll = collective_prims(jaxpr)
    if jp_coll:
        viol.append(Violation(
            "IRC001", name, 0,
            f"collective primitives in the update jaxpr: "
            f"{sorted({c for c, _ in jp_coll})}"))
    top, in_cond = dense_dot_counts(jaxpr, m * (n // p))
    if top or in_cond:
        viol.append(Violation(
            "IRC002", name, 0,
            f"{top + in_cond} dense passes in the O(n/p) update step "
            "(expected 0)"))
    viol += _hlo_host_violations(name, hlo)
    f64s = f64_introductions(jaxpr32)
    if f64s:
        viol.append(Violation(
            "IRC005", name, 0,
            f"f32 inputs produce f64 intermediates via {sorted(set(f64s))}"
            ))
    rec = {"hot_path": name, "p": p, "n": n,
           "collective_bytes": {k: float(v) for k, v in
                               coll.merged().items()},
           "collective_counts": dict(coll.count_by_kind),
           "budget_bytes": 0.0,
           "dense_passes": {"top": top, "cond": in_cond}}
    return HotPathResult(name, time.time() - t0, rec, viol)


def check_refresh_step(mesh, m: int = 8, n: int = 1 << 14) -> HotPathResult:
    """refresh_step: the sanctioned full-recompute site — at least one
    dense pass is REQUIRED here (d = c - Aᵀy; the A@xN rebuild may add a
    second), its collective traffic is O(m), and it stays f32-clean."""
    from repro.core.distributed import make_refresh_step
    t0 = time.time()
    label = _mesh_label(mesh)
    name = f"distributed.refresh_step@{label}"
    p = _mesh_p(mesh)
    viol: List[Violation] = []
    ref = make_refresh_step(mesh)
    axes = [a for a in ("pod", "data", "model") if a in mesh.shape]
    col_spec = P(None, tuple(axes))
    vec_spec = P(tuple(axes))
    rep = P()

    def abs_args(ft):
        f = lambda shape, dt=ft: jax.ShapeDtypeStruct(shape, dt)
        return (f((m, n)), f((n,)), f((n,)), f((n,)), f((n,)), f((m,)))

    in_sh = (NamedSharding(mesh, col_spec),) + tuple(
        NamedSharding(mesh, vec_spec) for _ in range(4)) + (
        NamedSharding(mesh, rep),)
    with mesh:
        compiled = jax.jit(ref, in_shardings=in_sh).lower(
            *abs_args(_F64)).compile()
        hlo = compiled.as_text()
        jaxpr = _jaxpr_of(ref, *abs_args(_F64))
        jaxpr32 = _jaxpr_of(ref, *abs_args(jnp.float32))
    coll = hlo_analysis.collective_bytes(hlo, default_group=p)
    top, in_cond = dense_dot_counts(jaxpr, m * (n // p))
    if top < 1:
        viol.append(Violation(
            "IRC002", name, 0,
            "refresh_step lowered with no dense pass — it IS the "
            "sanctioned d = c - A^T y recompute site"))
    if top > 2:
        viol.append(Violation(
            "IRC002", name, 0,
            f"{top} dense passes in refresh_step (expected <= 2: the d "
            "recompute and the A@xN basic-value rebuild)"))
    viol += _hlo_host_violations(name, hlo)
    f64s = f64_introductions(jaxpr32)
    if f64s:
        viol.append(Violation(
            "IRC005", name, 0,
            f"f32 inputs produce f64 intermediates via {sorted(set(f64s))}"
            ))
    rec = {"hot_path": name, "p": p, "n": n,
           "collective_bytes": {k: float(v) for k, v in
                               coll.merged().items()},
           "collective_counts": dict(coll.count_by_kind),
           "dense_passes": {"top": top, "cond": in_cond}}
    return HotPathResult(name, time.time() - t0, rec, viol)


def check_lp_twin(m: int = 4, N: int = 64, max_iters: int = 32
                  ) -> HotPathResult:
    """The jitted single-host LP twin (``lp._solve_lp_jax``): its pivot
    while-loop must contain no host callbacks (IRC003) and lowering with
    f32 operands must not promote to f64 (IRC005).  Trip-count recovery
    from the compiled HLO is recorded (the while bound must reflect the
    static ``max_iters``)."""
    from repro.core.lp import _solve_lp_jax
    t0 = time.time()
    name = f"lp.twin_step@m{m}_N{N}"
    viol: List[Violation] = []

    def abs_args(ft):
        f = lambda shape, dt=ft: jax.ShapeDtypeStruct(shape, dt)
        return (f((N,)), f((m, N)), f((N,)), f((N,)),
                jax.ShapeDtypeStruct((m,), jnp.int64),
                jax.ShapeDtypeStruct((N,), jnp.bool_))

    fn = lambda *a: _solve_lp_jax(*a, max_iters)
    compiled = jax.jit(fn).lower(*abs_args(_F64)).compile()
    hlo = compiled.as_text()
    jaxpr = _jaxpr_of(fn, *abs_args(_F64))
    jaxpr32 = _jaxpr_of(fn, *abs_args(jnp.float32))
    viol += _hlo_host_violations(name, hlo)
    viol += _callback_violations(name, jaxpr)
    f64s = f64_introductions(jaxpr32)
    if f64s:
        viol.append(Violation(
            "IRC005", name, 0,
            f"f32 inputs produce f64 intermediates via {sorted(set(f64s))}"
            ))
    trips = hlo_analysis.while_trip_counts(hlo)
    rec = {"hot_path": name, "m": m, "N": N,
           "while_trip_counts": {k: int(v) for k, v in trips.items()},
           "max_trip": int(max(trips.values())) if trips else 0}
    return HotPathResult(name, time.time() - t0, rec, viol)


def check_lp_batch(m: int = 4, n: int = 16, K: int = 4,
                   max_iters: int = 16) -> HotPathResult:
    """The batched bound-variant LP engine (``lp_batch._batched_core``):
    a single-device batch, not an SPMD program, so it must lower with
    ZERO collectives (IRC001), no host callbacks/transfers inside the
    vmapped pivot while-loop (IRC003), and f32 operands must not
    silently promote to f64 (IRC005).  Shapes are one (m, n, K) shape
    class; the while trip bound must reflect the static per-lane cap."""
    from repro.core.lp_batch import _batched_core
    t0 = time.time()
    N = n + m
    name = f"lp_batch.core@m{m}_n{n}_K{K}"
    viol: List[Violation] = []
    core = _batched_core(m, n, K, max_iters, 64)

    def abs_args(ft):
        f = lambda shape, dt=ft: jax.ShapeDtypeStruct(shape, dt)
        # single packed operand: l | u | tol | basis0 | at_upper0 |
        # valid | pivot_cap — see _batched_core
        return (f((N,)), f((m, N)), f((K, 3 * N + m + 3)))

    compiled = core.lower(*abs_args(_F64)).compile()
    hlo = compiled.as_text()
    jaxpr = _jaxpr_of(core, *abs_args(_F64))
    jaxpr32 = _jaxpr_of(core, *abs_args(jnp.float32))
    jp_coll = collective_prims(jaxpr)
    if jp_coll:
        viol.append(Violation(
            "IRC001", name, 0,
            f"collective primitives in the batched LP core: "
            f"{sorted({c for c, _ in jp_coll})} — the wave solver is a "
            "single-device vmap, not an SPMD program"))
    viol += _hlo_host_violations(name, hlo)
    viol += _callback_violations(name, jaxpr)
    f64s = f64_introductions(jaxpr32)
    if f64s:
        viol.append(Violation(
            "IRC005", name, 0,
            f"f32 inputs produce f64 intermediates via {sorted(set(f64s))}"
            ))
    trips = hlo_analysis.while_trip_counts(hlo)
    rec = {"hot_path": name, "m": m, "n": n, "K": K,
           "while_trip_counts": {k: int(v) for k, v in trips.items()},
           "max_trip": int(max(trips.values())) if trips else 0}
    return HotPathResult(name, time.time() - t0, rec, viol)


def check_kernel_pricing(m: int = 4, n: int = 4096) -> HotPathResult:
    """The Pallas pricing kernel, jaxpr level only: interpret-mode Pallas
    may legitimately lower to host callbacks in HLO, so the contract here
    is dtype preservation plus no callback primitives OUTSIDE the
    pallas_call itself."""
    from repro.kernels.ops import interpret_kernels
    from repro.kernels.pricing import pricing
    t0 = time.time()
    name = f"kernels.pricing@m{m}_n{n}"
    viol: List[Violation] = []

    def args(ft):
        f = lambda shape, dt=ft: jax.ShapeDtypeStruct(shape, dt)
        return (f((m, n)), f((m,)), f((n,)),
                jax.ShapeDtypeStruct((n,), jnp.int32),
                f((n,)), f((n,)), f(()))

    fn = lambda *a: pricing(*a, interpret=interpret_kernels())
    jaxpr32 = _jaxpr_of(fn, *args(jnp.float32))
    jaxpr = _jaxpr_of(fn, *args(_F64))
    f64s = f64_introductions(jaxpr32)
    if f64s:
        viol.append(Violation(
            "IRC005", name, 0,
            f"f32 inputs produce f64 intermediates via {sorted(set(f64s))}"
            ))
    for prim, ctx in callback_prims(jaxpr):
        if not any("pallas" in c for c in ctx):
            viol.append(Violation(
                "IRC003", name, 0,
                f"callback primitive {prim} outside the pallas_call "
                f"(ctx={'/'.join(ctx)})"))
    rec = {"hot_path": name, "m": m, "n": n}
    return HotPathResult(name, time.time() - t0, rec, viol)


def check_kernel_segstats(n: int = 4096, k: int = 4) -> HotPathResult:
    """The Pallas segment-stats kernel: f32 accumulation is BY DESIGN
    (preferred_element_type=f32) — the contract is that f32 inputs never
    promote to f64, and no callbacks escape the pallas_call."""
    from repro.kernels.ops import interpret_kernels
    from repro.kernels.segstats import segstats_partials
    t0 = time.time()
    name = f"kernels.segstats@n{n}_k{k}"
    viol: List[Violation] = []
    fn = lambda v, i: segstats_partials(v, i, interpret=interpret_kernels())
    a32 = (jax.ShapeDtypeStruct((n, k), jnp.float32),
           jax.ShapeDtypeStruct((n,), jnp.int32))
    jaxpr32 = _jaxpr_of(fn, *a32)
    f64s = f64_introductions(jaxpr32)
    if f64s:
        viol.append(Violation(
            "IRC005", name, 0,
            f"f32 inputs produce f64 intermediates via {sorted(set(f64s))}"
            ))
    for prim, ctx in callback_prims(jaxpr32):
        if not any("pallas" in c for c in ctx):
            viol.append(Violation(
                "IRC003", name, 0,
                f"callback primitive {prim} outside the pallas_call "
                f"(ctx={'/'.join(ctx)})"))
    rec = {"hot_path": name, "n": n, "k": k}
    return HotPathResult(name, time.time() - t0, rec, viol)


def check_split_descent(batch: int = 1024, nodes: int = 31,
                        bounds_per: int = 3) -> HotPathResult:
    """Batched split-tree descent (``partitioner._descend_batch_jax``):
    the nested while loops (tree levels x bisection) must not host-sync
    per level (IRC003) and must not promote f32 tuple values (IRC005)."""
    from repro.core.partitioner import _descend_batch_jax
    t0 = time.time()
    name = f"partitioner.descend_batch@b{batch}_N{nodes}"
    viol: List[Violation] = []
    B = nodes * bounds_per

    def args(ft):
        return (jax.ShapeDtypeStruct((nodes,), jnp.int32),
                jax.ShapeDtypeStruct((nodes + 1,), jnp.int64),
                jax.ShapeDtypeStruct((B,), ft),
                jax.ShapeDtypeStruct((B + nodes,), jnp.int64),
                jax.ShapeDtypeStruct((), jnp.int64),
                jax.ShapeDtypeStruct((batch, 4), ft))

    fn = lambda *a: _descend_batch_jax(*a)
    compiled = jax.jit(fn).lower(*args(_F64)).compile()
    hlo = compiled.as_text()
    jaxpr = _jaxpr_of(fn, *args(_F64))
    jaxpr32 = _jaxpr_of(fn, *args(jnp.float32))
    viol += _hlo_host_violations(name, hlo)
    viol += _callback_violations(name, jaxpr)
    f64s = f64_introductions(jaxpr32)
    if f64s:
        viol.append(Violation(
            "IRC005", name, 0,
            f"f32 tuples promote to f64 via {sorted(set(f64s))}"))
    rec = {"hot_path": name, "batch": batch, "nodes": nodes}
    return HotPathResult(name, time.time() - t0, rec, viol)


# -------------------------------------------------------------- mesh grids


def _host_meshes():
    """Meshes buildable on the 4 forced host devices (tier-1 tests)."""
    metas = []
    if len(jax.devices()) >= 2:
        metas.append(jax.make_mesh((1, 2), ("data", "model")))
    if len(jax.devices()) >= 4:
        metas.append(jax.make_mesh((2, 2), ("data", "model")))
    return metas


def _pod_meshes():
    """The production pod grid (needs 512 forced host devices — the CLI
    sets XLA_FLAGS before importing jax, like launch/dryrun.py)."""
    from repro.launch.mesh import make_production_mesh
    return [make_production_mesh(multi_pod=False),
            make_production_mesh(multi_pod=True)]


GRID_SHAPES = {
    # grid -> (m, n) for the distributed steps; n divisible by every p
    "host": (8, 1 << 14),
    "pod": (8, 1 << 20),
}


def run_contracts(grid: str = "host"
                  ) -> Tuple[List[Violation], List[dict], float]:
    """Run every hot-path check over the requested mesh grid.

    ``grid='none'`` skips the mesh-dependent checks (lint-only CI lanes);
    ``'host'`` uses the forced-host-device meshes the tier-1 tests use;
    ``'pod'`` lowers for the production 16x16 / 2x16x16 meshes.
    Returns (violations, per-hot-path records, total wall seconds).
    """
    t0 = time.time()
    results: List[HotPathResult] = []
    if grid != "none":
        m, n = GRID_SHAPES[grid]
        meshes = _host_meshes() if grid == "host" else _pod_meshes()
        for mesh in meshes:
            results.append(check_pq_step(mesh, m, n))
            results.append(check_update_step(mesh, m, n))
            results.append(check_refresh_step(mesh, m, n))
    results.append(check_lp_twin())
    results.append(check_lp_batch())
    results.append(check_kernel_pricing())
    results.append(check_kernel_segstats())
    results.append(check_split_descent())
    violations = [v for r in results for v in r.violations]
    records = [dict(r.record, wall_s=round(r.wall_s, 3)) for r in results]
    return violations, records, time.time() - t0
