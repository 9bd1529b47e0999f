"""Trip-count-aware HLO analysis tests — the roofline's foundation.

The analyzer must recover loop multipliers, dot FLOPs, fusion-granularity
bytes, in-place DUS traffic and collective operand bytes from optimized HLO
text.  Synthetic-module tests pin the parser; a live grad-of-scan compile
pins the end-to-end count.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_flops import (_shape_bytes, analyse_hlo,
                                    parse_computations)

SYNTH = """
HloModule synth

%body.1 (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %p = (s32[], f32[64,64]) parameter(0)
  %it = s32[] get-tuple-element(%p), index=0
  %x = f32[64,64]{1,0} get-tuple-element(%p), index=1
  %d = f32[64,64]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[64,64]{1,0} all-reduce(%d), replica_groups={}
  ROOT %t = (s32[], f32[64,64]) tuple(%it, %ar)
}

%cond.1 (p: (s32[], f32[64,64])) -> pred[] {
  %p = (s32[], f32[64,64]) parameter(0)
  %it = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%it, %c), direction=LT
}

ENTRY %main.1 (a: f32[64,64]) -> f32[64,64] {
  %a = f32[64,64]{1,0} parameter(0)
  %iv = s32[] constant(0)
  %t0 = (s32[], f32[64,64]) tuple(%iv, %a)
  %w = (s32[], f32[64,64]) while(%t0), condition=%cond.1, body=%body.1
  ROOT %out = f32[64,64]{1,0} get-tuple-element(%w), index=1
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[64,64]{1,0}") == 64 * 64 * 4
    assert _shape_bytes("bf16[8,128]") == 8 * 128 * 2
    assert _shape_bytes("(s32[], f32[4,4])") == 4 + 64
    assert _shape_bytes("pred[]") == 1


def test_parse_computations_synthetic():
    comps = parse_computations(SYNTH)
    assert set(comps) == {"body.1", "cond.1", "main.1"}
    assert any(i.op == "while" for i in comps["main.1"].instrs)


def test_trip_count_and_collectives_synthetic():
    st = analyse_hlo(SYNTH)
    assert st.while_trip_counts == [12]
    assert st.flops == 12 * 2 * 64 ** 3            # dot x trip count
    assert st.collective_counts == {"all-reduce": 12}
    assert st.collective_bytes == 12 * 64 * 64 * 4


def test_live_grad_of_scan_exact():
    def f(xs, w):
        def body(c, x):
            return c @ w + x, None
        out, _ = jax.lax.scan(body, xs[0], xs)
        return (out.astype(jnp.float32) ** 2).sum()

    lowered = jax.jit(jax.grad(f, argnums=(0, 1))).lower(
        jax.ShapeDtypeStruct((16, 64, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 64), jnp.float32))
    st = analyse_hlo(lowered.compile().as_text())
    expected = 16 * (2 * 64 ** 3) * 3              # fwd + 2 bwd dots x 16
    assert st.flops == pytest.approx(expected, rel=0.02)
    assert sorted(st.while_trip_counts) == [16, 16]


def test_dus_counts_slice_not_buffer():
    text = """
HloModule dus

ENTRY %main.2 (buf: f32[1024,1024], upd: f32[1,1024]) -> f32[1024,1024] {
  %buf = f32[1024,1024]{1,0} parameter(0)
  %upd = f32[1,1024]{1,0} parameter(1)
  %i = s32[] constant(5)
  %z = s32[] constant(0)
  ROOT %d = f32[1024,1024]{1,0} dynamic-update-slice(%buf, %upd, %i, %z)
}
"""
    st = analyse_hlo(text)
    # ~2 x update bytes (+ scalar indices), NOT ~2 x 4MB buffer
    assert 2 * 1024 * 4 <= st.bytes_accessed < 2 * 1024 * 4 + 64


def test_roofline_terms_refuse_unknown_target():
    from repro.launch.hlo_analysis import roofline_terms
    terms = roofline_terms(197e12, 819e9, 0.0, 1, target="tpu_v5e")
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["memory_s"] == pytest.approx(1.0)
    with pytest.raises(KeyError, match="no roofline peaks"):
        roofline_terms(1.0, 1.0, 0.0, 1, target="tpu_v9")
