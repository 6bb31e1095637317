"""The Pallas kernels compile for a TPU v5e that is described, not attached.

The TPU compiler is installed with jaxlib, so the kernels of the main path
are compiled here at the shapes chip_smoke.py runs them at on the chip —
their capture catalog shapes (capture/workloads.py) and one deployment
width each — and each compiled program must call its kernel
(``tpu_custom_call``).  This catches what interpret mode cannot: block
shapes the TPU lowering refuses, primitives it does not implement, VMEM
overflow.  A pass is a compile, never a run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and collection in every
test worker must see the same tests.
"""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import load_chip_smoke
from repro.configs import get_config
from repro.kernels.block_quant.block_quant import BLOCK, dequantize_pallas, quantize_pallas
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan.mamba_scan import selective_scan_pallas
from repro.launch import steps
from repro.models import model as M
from repro.models import nn, scopes

_SMOKE = load_chip_smoke()
FLASH_CASES = {label: case for label, *case in _SMOKE.flash_cases()}
BLOCK_QUANT_CASES = dict(_SMOKE.block_quant_cases())
MAMBA_CASES = dict(_SMOKE.mamba_cases())


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to compile for
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("name", list(FLASH_CASES))
def test_flash_attention_compiles_for_v5e(one_chip, name):
    (b, sq, skv, h, kvh, d), dtype, causal, window, bq = FLASH_CASES[name]
    q = jax.ShapeDtypeStruct((b, sq, h, d), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, skv, kvh, d), dtype, sharding=one_chip)
    hlo = _compiled_hlo(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=causal, window=window,
                                               bq=bq),
        q, kv, kv)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name", list(BLOCK_QUANT_CASES))
def test_block_quant_compiles_for_v5e(one_chip, name):
    r, c = BLOCK_QUANT_CASES[name]
    x = jax.ShapeDtypeStruct((r, c), jnp.float32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((r, c), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((r, c // BLOCK), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_hlo(quantize_pallas, x)
    assert "tpu_custom_call" in _compiled_hlo(
        lambda q, s: dequantize_pallas(q, s, jnp.bfloat16), q, s)


@pytest.mark.parametrize("name", list(MAMBA_CASES))
def test_mamba_scan_compiles_for_v5e(one_chip, name):
    b, s, d, n = MAMBA_CASES[name]

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    hlo = _compiled_hlo(selective_scan_pallas, f32(b, s, d), f32(d, n), f32(b, s, n),
                        f32(b, s, n), f32(b, s, d))
    assert "tpu_custom_call" in hlo



# danube at full width and 2 of its 24 layers, served as the chip benchmark's
# chat-decode cell serves it: batches of 16, a 1024-token prompt grown by 256
DANUBE_LAYERS, BATCH, PROMPT, GEN = 2, 16, 1024, 256


def _danube_programs(one_chip):
    """Compiled text of danube's prefill and decode step for the described chip."""
    cfg = dataclasses.replace(get_config("h2o-danube-1.8b"), num_layers=DANUBE_LAYERS)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: sds(s.shape, jnp.bfloat16),
                          nn.abstract_params(M.model_specs(cfg)))
    kv = sds((DANUBE_LAYERS, BATCH, PROMPT + GEN, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
    prefill = _compiled_hlo(lambda p, b: M.prefill(cfg, p, b), params,
                            {"tokens": sds((BATCH, PROMPT), jnp.int32)})
    decode = jax.jit(steps.make_decode_step(cfg), donate_argnums=(1,)).lower(
        params, {"seg0": {"k": kv, "v": kv}}, sds((BATCH,), jnp.int32),
        sds((), jnp.int32)).compile().as_text()
    return {"prefill": prefill, "decode_step": decode}


def _instructions(text, table):
    """(name, opcode, result type) of each instruction that ``table`` labels."""
    out = []
    for line in text.splitlines():
        m = scopes._INSTRUCTION.match(line)
        if m and m.group(1) in table:
            result = line.split("=", 1)[1].split("{", 1)[0].strip()
            out.append((m.group(1), m.group(2), result))
    return out


@pytest.fixture(scope="module")
def danube(one_chip):
    return _danube_programs(one_chip)


def _producer(lines, name):
    """The entry instruction that ``name`` stands for, followed through the
    tuple a ``while`` passes on unchanged: (opcode, line)."""
    while True:
        line = lines[name]
        op = scopes._INSTRUCTION.match(line).group(2)
        m = re.search(r"get-tuple-element\(%([\w.\-]+)\), index=(\d+)", line)
        if op != "get-tuple-element" or not m:
            return op, line
        loop = re.search(r" while\(%([\w.\-]+)\)", lines[m.group(1)])
        state = re.search(r" tuple\(([^)]*)\)", lines[loop.group(1)])
        name = state.group(1).split(", ")[int(m.group(2))].split("%")[-1]


def test_decode_step_parts_for_v5e(danube):
    """Decode attention reads the cache where it lies: no instruction of the
    compiled decode step holds K or V per query head (the 4x-head f32 copy
    ``repeat_kv`` made); both attention einsums are convolutions labelled
    ``attn_core``; no top-level copy or dynamic-slice fusion has the shape of
    the stacked cache or of one layer's slab (the layer scan once cut every
    layer's slab out, copied it to the attention's layout and re-stacked it,
    each step); the token is written by exactly two whole-cache
    dynamic-update-slices in the entry, labelled ``attn_core``, on the
    donated cache parameters, which the module aliases to its cache
    outputs, so the write is in place."""
    text = danube["decode_step"]
    table = scopes.op_scopes(text)
    ins = _instructions(text, table)
    h, kvh, dh, slots = 32, 8, 80, PROMPT + GEN
    per_head = (rf"(f32|bf16)\[{BATCH},{slots},{kvh},{h // kvh},{dh}\]",
                rf"(f32|bf16)\[{BATCH},{slots},{h},{dh}\]")
    assert not [m.group(0) for pat in per_head for m in re.finditer(pat, text)]
    convs = re.findall(r"= \S+ convolution\(.*?op_name=\"([^\"]*)\"", text)
    for spec in ("bkgd,bskd->bkgs", "bkgs,bskd->bkgd"):
        assert [n for n in convs if f"/{spec}/" in n and scopes.label(n) == "attn_core"], spec
        holders = [n for n, op, _ in ins if op == "fusion" and re.search(
            rf"%{re.escape(n)} = .*op_name=\"[^\"]*/{spec}/", text)]
        assert holders and {table[n] for n in holders} == {"attn_core"}, spec

    stacked = f"bf16[{DANUBE_LAYERS},{BATCH},{slots},{kvh},{dh}]"
    slab = re.compile(rf"\w+\[(1,)?{BATCH},{slots},{kvh},{dh}\]$")
    moved = [n for n, op, r in ins if (r == stacked or slab.match(r)) and (
        op in ("copy", "dynamic-slice") or op == "fusion" and "dynamic-slice" in n)]
    assert moved == []

    comps, entry = scopes._computations(text)
    lines = {scopes._INSTRUCTION.match(line).group(1): line for line in comps[entry]
             if scopes._INSTRUCTION.match(line)}
    writes = [n for n, op, r in ins if "dynamic-update-slice" in f"{n} {op}" and r == stacked]
    assert len(writes) == 2 and set(writes) <= set(lines)
    assert {table[n] for n in writes} == {"attn_core"}
    params = {}
    for n in writes:
        operand = re.search(r"dynamic-update-slice\(%([\w.\-]+)", lines[n]).group(1)
        op, line = _producer(lines, operand)
        assert op == "parameter" and "op_name=\"cache[" in line, n
        params[n] = int(re.search(r"parameter\((\d+)\)", line).group(1))
    aliases = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}", text.split("\n", 1)[0]))
    root = next(line for line in comps[entry] if line.lstrip().startswith("ROOT"))
    outputs = [o.split("%")[-1] for o in re.search(r" tuple\(([^)]*)\)", root).group(1).split(", ")]
    assert {n: int(aliases[str(outputs.index(n))]) for n in writes} == params


def _without_metadata(text):
    """The program text with each instruction's metadata and the debug-info
    tables it points into taken out, and instruction names numbered in
    order of first use (a scope moves the counters that number them)."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(\d+ .*\n)*",
                  "\n", text)
    names = {}
    return re.sub(r"%([\w.\-]+)",
                  lambda m: "%" + names.setdefault(m.group(1), f"i{len(names)}"), text)


def test_scopes_add_only_metadata_for_v5e(one_chip, danube):
    """With tracing off the scopes cost nothing: without them the compiled
    prefill and decode step are the same programs."""
    scoped = danube
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = _danube_programs(one_chip)
    for name in scoped:
        assert "attn_core" in scoped[name] and "attn_core" not in bare[name]
        assert _without_metadata(scoped[name]) == _without_metadata(bare[name]), name
