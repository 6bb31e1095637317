"""The serving step's named parts (``models/scopes.py``) on the CPU: every
instruction of a compiled danube-family prefill and decode step that carries
an op_name falls in one of the scopes, and the parts that a trace reads
are all there."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.launch import steps
from repro.models import model as M
from repro.models import nn, scopes

B, S = 2, 32


def _compiled(program):
    cfg = get_config("h2o-danube-1.8b").reduced()
    params = nn.abstract_params(M.model_specs(cfg))
    pre = jax.jit(lambda p, b: M.prefill(cfg, p, b)).lower(
        params, {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}).compile()
    if program == "prefill":
        return pre.as_text()
    _, cache = pre.out_info
    cache = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), cache)
    return jax.jit(steps.make_decode_step(cfg)).lower(
        params, cache, jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_every_named_instruction_has_a_scope(program):
    text = _compiled(program)
    table = scopes.op_scopes(text)
    comps, _ = scopes._computations(text)
    named = []
    for line in text.splitlines():
        m = scopes._INSTRUCTION.match(line)
        if m and m.group(1) in table and m.group(2) != "parameter" \
                and scopes._op_name(line, m.group(2), comps):
            named.append(m.group(1))
    assert named
    assert [n for n in named if table[n] == scopes.UNSCOPED] == []
    assert set(table.values()) <= set(scopes.LABELS)
    want = {"attn_core", "attn_proj", "mlp", "lm_head", "embed", "layer_loop"}
    if program == "decode_step":
        want.add("sample")
    assert want <= set(table.values())


def test_labels_from_op_names():
    assert scopes.label("jit(decode_step)/layers/while/body/closed_call/attn_core/"
                        "broadcast_in_dim") == "attn_core"
    assert scopes.label("jit(decode_step)/layers/while/body/dynamic_slice") == "layer_loop"
    assert scopes.label("jit(decode_step)/layers/while") == "layer_loop"
    assert scopes.label("jit(decode_step)/sample/argmax") == "sample"
    assert scopes.label("params['embed']") == scopes.UNSCOPED
    assert scopes.label(None) == scopes.UNSCOPED
    with pytest.raises(ValueError, match="unknown scope"):
        scopes.scope("attention")


def test_only_top_level_instructions_are_labelled():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/mlp/mul"}
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%p), index=1
  %fusion.2 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation
  %copy.3 = f32[4]{0} copy(%fusion.2)
  ROOT %tuple.4 = (s32[], f32[4]{0}) tuple(%gte.1, %copy.3), metadata={op_name="jit(f)/layers/while/body/tuple"}
}

%cond (p: (s32[], f32[4])) -> pred[] {
  %p = (s32[], f32[4]{0}) parameter(0)
  ROOT %constant.5 = pred[] constant(false)
}

ENTRY %main.6 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %while.7 = (s32[], f32[4]{0}) while(%x), condition=%cond, body=%body, metadata={op_name="jit(f)/layers/while"}
  ROOT %gte.8 = f32[4]{0} get-tuple-element(%while.7), index=1
}
"""
    table = scopes.op_scopes(text)
    assert "multiply.1" not in table and "param_0" not in table
    assert table["fusion.2"] == "mlp"  # a bare fusion takes its inner op_name
    assert table["copy.3"] == scopes.UNSCOPED
    assert table["tuple.4"] == table["while.7"] == scopes.LAYER_LOOP
    assert table["constant.5"] == scopes.UNSCOPED
