"""Unit tests for the static HLO walkers on hand-written HLO text:
trip-count recovery, the per-collective byte model (both replica_groups
forms, async start/done pairs, while weighting), the host-transfer /
python-callback walker, and the per-while-body per-trip stats."""
import pytest

from repro.distributed.hlo_analysis import (collective_bytes, hlo_stats,
                                            host_transfer_ops, shape_bytes,
                                            while_body_stats,
                                            while_trip_counts)

# 25-trip scan whose body issues one all-reduce (explicit 4-wide groups),
# one all-gather (iota groups, 8-wide) and an async all-reduce pair; one
# collective-permute outside the loop.
LOOP_HLO = """\
HloModule loop_fixture

%cond.1 (arg.1: (s32[], f64[128])) -> pred[] {
  %arg.1 = (s32[], f64[128]) parameter(0)
  %iv = s32[] get-tuple-element(%arg.1), index=0
  %small = s32[] constant(3)
  %limit = s32[] constant(25)
  ROOT %lt = pred[] compare(%iv, %limit), direction=LT
}

%body.1 (arg.2: (s32[], f64[128])) -> (s32[], f64[128]) {
  %arg.2 = (s32[], f64[128]) parameter(0)
  %x = f64[128] get-tuple-element(%arg.2), index=1
  %ar = f64[128] all-reduce(%x), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%sum
  %ag = f64[512]{0} all-gather(%x), replica_groups=[4,8]<=[32], dimensions={0}
  %ars = f64[32] all-reduce-start(%x), replica_groups={{0,1,2,3}}, to_apply=%sum
  %ard = f64[32] all-reduce-done(%ars)
  %iv.2 = s32[] get-tuple-element(%arg.2), index=0
  ROOT %t = (s32[], f64[128]) tuple(%iv.2, %ar)
}

ENTRY %main (p0: f64[128]) -> f64[128] {
  %p0 = f64[128] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f64[128]) tuple(%zero, %p0)
  %w = (s32[], f64[128]) while(%init), condition=%cond.1, body=%body.1
  %cp = f64[64] collective-permute(%p0), source_target_pairs={{0,1},{1,0}}
  ROOT %out = f64[128] get-tuple-element(%w), index=1
}
"""

# A 7-trip loop containing a python-callback custom-call and an outfeed,
# plus a benign Sharding custom-call and a top-level (not-in-loop)
# callback in ENTRY.
HOST_HLO = """\
HloModule host_fixture

%cond.2 (arg.1: (s32[], f32[4])) -> pred[] {
  %arg.1 = (s32[], f32[4]) parameter(0)
  %iv = s32[] get-tuple-element(%arg.1), index=0
  %limit = s32[] constant(7)
  ROOT %lt = pred[] compare(%iv, %limit), direction=LT
}

%body.2 (arg.2: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg.2 = (s32[], f32[4]) parameter(0)
  %x = f32[4] get-tuple-element(%arg.2), index=1
  %cb = f32[4] custom-call(%x), custom_call_target="xla_python_cpu_callback"
  %shard = f32[4] custom-call(%cb), custom_call_target="Sharding"
  %tok = token[] after-all()
  %of = token[] outfeed(%x, %tok)
  %iv.2 = s32[] get-tuple-element(%arg.2), index=0
  ROOT %t = (s32[], f32[4]) tuple(%iv.2, %cb)
}

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[4]) tuple(%zero, %p0)
  %w = (s32[], f32[4]) while(%init), condition=%cond.2, body=%body.2
  %top = f32[4] custom-call(%p0), custom_call_target="SomeHostTransfer"
  ROOT %out = f32[4] get-tuple-element(%w), index=1
}
"""

# 10-trip loop around one dot: f64[8,32] @ f64[32,16].
DOT_HLO = """\
HloModule dot_fixture

%cond.3 (arg.1: (s32[], f64[8,16])) -> pred[] {
  %arg.1 = (s32[], f64[8,16]) parameter(0)
  %iv = s32[] get-tuple-element(%arg.1), index=0
  %limit = s32[] constant(10)
  ROOT %lt = pred[] compare(%iv, %limit), direction=LT
}

%body.3 (arg.2: (s32[], f64[8,16])) -> (s32[], f64[8,16]) {
  %arg.2 = (s32[], f64[8,16]) parameter(0)
  %a = f64[8,32] parameter(1)
  %b = f64[32,16] parameter(2)
  %d = f64[8,16] dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %iv.2 = s32[] get-tuple-element(%arg.2), index=0
  ROOT %t = (s32[], f64[8,16]) tuple(%iv.2, %d)
}

ENTRY %main (p0: f64[8,16]) -> f64[8,16] {
  %p0 = f64[8,16] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f64[8,16]) tuple(%zero, %p0)
  %w = (s32[], f64[8,16]) while(%init), condition=%cond.3, body=%body.3
  ROOT %out = f64[8,16] get-tuple-element(%w), index=1
}
"""


def test_shape_bytes():
    assert shape_bytes("f64[128]") == 1024
    assert shape_bytes("f32[4,4]") == 64
    assert shape_bytes("pred[8]") == 8
    assert shape_bytes("(f32[10], pred[2])") == 42
    assert shape_bytes("s32[]") == 4
    assert shape_bytes("token[]") == 0


def test_shape_bytes_of_compiled_text_forms():
    assert shape_bytes("f32[4,8]{1,0}") == 128
    assert shape_bytes("bf16[2,3]") == 12
    assert shape_bytes("(f32[2], s32[4])") == 24
    assert shape_bytes("pred[]") == 1


def test_trip_count_recovery_takes_loop_bound():
    # the condition holds two constants (3 and 25); the bound is the max
    assert while_trip_counts(LOOP_HLO) == {"body.1": 25}
    assert while_trip_counts(HOST_HLO) == {"body.2": 7}


def test_collective_byte_model_with_while_weighting():
    st = collective_bytes(LOOP_HLO)
    # all-reduce: explicit groups of 4 -> 2*(4-1)/4 per byte.  Per trip:
    # f64[128] (1024 B) plus the async f64[32] start/done pair counted
    # once (256 B); x25 trips.
    assert st.bytes_by_kind["all-reduce"] == pytest.approx(
        25 * (1024 + 256) * 1.5)
    # all-gather: iota groups [4,8]<=[32] -> group size 8 -> 7/8
    assert st.bytes_by_kind["all-gather"] == pytest.approx(
        25 * 4096 * 7 / 8)
    # collective-permute outside the loop: counted once, factor 1
    assert st.bytes_by_kind["collective-permute"] == pytest.approx(512)
    assert st.count_by_kind == {"all-reduce": 50, "all-gather": 25,
                                "collective-permute": 1}
    assert st.total_bytes == pytest.approx(sum(st.bytes_by_kind.values()))


def test_collective_default_group_size():
    # strip replica_groups annotations -> the caller-declared default
    import re
    hlo = re.sub(r"replica_groups=(\{\{[^}]*\}[^=]*\}|\[[\dx,]+\]<=\[\d+\])",
                 "channel_id=1", LOOP_HLO)
    st2 = collective_bytes(hlo, default_group=2)
    assert st2.bytes_by_kind["all-reduce"] == pytest.approx(
        25 * (1024 + 256) * 1.0)          # 2(n-1)/n = 1 at n=2
    assert st2.bytes_by_kind["all-gather"] == pytest.approx(
        25 * 4096 * 0.5)


def test_host_transfer_walker_finds_callbacks_in_loops():
    ops = host_transfer_ops(HOST_HLO)
    by_op = {(o["op"], o["target"]): o for o in ops}
    cb = by_op[("custom-call", "xla_python_cpu_callback")]
    assert cb["in_while"] and cb["trips"] == 7
    assert cb["computation"] == "body.2"
    of = by_op[("outfeed", "")]
    assert of["in_while"] and of["trips"] == 7
    top = by_op[("custom-call", "SomeHostTransfer")]
    assert not top["in_while"] and top["trips"] == 1
    # the Sharding custom-call is benign and must NOT be reported
    assert not any(o["target"] == "Sharding" for o in ops)


def test_host_transfer_walker_clean_module():
    assert host_transfer_ops(LOOP_HLO) == []


def test_while_body_stats_per_trip():
    stats = while_body_stats(LOOP_HLO)
    trips, st = stats["body.1"]
    assert trips == 25
    # per-trip (un-multiplied) bytes
    assert st.bytes_by_kind["all-reduce"] == pytest.approx(
        (1024 + 256) * 1.5)
    assert st.bytes_by_kind["all-gather"] == pytest.approx(4096 * 7 / 8)
    assert "collective-permute" not in st.bytes_by_kind
    assert st.count_by_kind == {"all-reduce": 2, "all-gather": 1}


def test_hlo_stats_dot_flops_while_weighted():
    st = hlo_stats(DOT_HLO)
    # dot: out 8x16, contraction 32 -> 2*128*32 flops, x10 trips
    assert st.flops == pytest.approx(10 * 2 * 128 * 32)
    # operand + result bytes: f64[8,32] + f64[32,16] + f64[8,16]
    assert st.dot_bytes == pytest.approx(10 * (2048 + 4096 + 1024))


def test_hlo_stats_counts_scanned_dots():
    """A real compile: a scan of L matmuls counts L dots' FLOPs."""
    import jax
    import jax.numpy as jnp
    L, d = 8, 64
    W = jnp.ones((L, d, d), jnp.float32)
    x = jnp.ones((4, d), jnp.float32)

    def f(x, W):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, W)[0]

    hlo = jax.jit(f).lower(x, W).compile().as_text()
    st = hlo_stats(hlo)
    assert st.flops == pytest.approx(L * 2 * 4 * d * d)


def test_collective_parser_finds_none_on_one_device():
    import jax
    import jax.numpy as jnp
    x = jnp.ones((8, 8))

    @jax.jit
    def f(a):
        return a.sum()

    hlo = f.lower(x).compile().as_text()
    assert collective_bytes(hlo).total_bytes == 0.0


def test_real_lowering_roundtrip():
    """The walkers agree with an actual jax lowering: a psum inside a
    scan over a 2-device mesh produces a while whose recovered trip
    count matches the scan length, with all-reduce traffic to match."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = jax.make_mesh((2,), ("data",))
    L = 6

    def fn(x):
        def body(c, _):
            s = jax.lax.psum(c, "data")
            return c + 1e-3 * s, ()
        c, _ = jax.lax.scan(body, x, None, length=L)
        return c

    from jax import shard_map
    sm = shard_map(fn, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    hlo = jax.jit(sm).lower(
        jax.ShapeDtypeStruct((8,), jnp.float64)).compile().as_text()
    trips = while_trip_counts(hlo)
    assert max(trips.values()) == L
    st = collective_bytes(hlo, default_group=2)
    assert st.count_by_kind.get("all-reduce", 0) >= L
    assert st.bytes_by_kind["all-reduce"] > 0
