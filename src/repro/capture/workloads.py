"""The captured-kernel workload catalog (DESIGN.md §2.8).

Four representative launches of the repo's Pallas kernels, registered as
first-class DS-simulator workloads at ``repro.core.sim`` import time:

  fa_prefill  flash attention, 512-token GQA prefill — Q/O tiles parked
              across the streamed K/V loop (tile reuse + streaming)
  fa_decode   flash attention, batched single-token decode — tiny Q, the
              whole KV cache streamed per head (read-dominated scan)
  mamba_fwd   chunked selective scan — A parked per channel tile, B/C
              re-streamed for every channel tile, chunk I/O + y writeback
  bq_quant    per-block absmax int8 quantize — whole-row f32 tile reads,
              int8 payload + f32 scale writes (the compressible one)

Registration is import-cheap: geometry shims live in each kernel's
``ops.py`` (which imports jax), so the catalog defers that import to the
first actual use — building a trace or resolving the measured
compressibility — and caches the capture per process.  Replay semantics
(``seed`` rotates phase, ``n`` truncates/tiles, ``footprint`` is ignored —
the geometry is authoritative) are shared with ``.npz`` trace files via
:func:`repro.core.sim.trace.replay_slice`, and '+'-mix composition works
like any other registered workload.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Dict, Tuple

from repro.capture.recorder import CaptureResult, KernelTraceRecorder


@dataclass(frozen=True)
class CapturedKernel:
    """Catalog entry: a named kernel launch whose geometry is built lazily
    (``module`` is imported — pulling in jax — only on first capture)."""

    name: str
    module: str  # the kernel's ops module carrying the trace_geometry shim
    config: Dict[str, object]  # kwargs for the shim
    description: str = ""

    def build_geometry(self):
        ops = importlib.import_module(self.module)
        return ops.trace_geometry(**self.config)


CAPTURED: Dict[str, CapturedKernel] = {}
_RESULTS: Dict[str, CaptureResult] = {}  # per-process capture cache


def _catalog(name: str, module: str, description: str, **config) -> None:
    CAPTURED[name] = CapturedKernel(name=name, module=module, config=config,
                                    description=description)


_FA = "repro.kernels.flash_attention.ops"
_MS = "repro.kernels.mamba_scan.ops"
_BQ = "repro.kernels.block_quant.ops"

_catalog("fa_prefill", _FA,
         "captured flash_attention prefill (GQA, Q parked over KV stream)",
         b=1, sq=512, skv=512, h=4, kvh=2, d=64, variant="prefill")
_catalog("fa_decode", _FA,
         "captured flash_attention decode (KV cache streamed per head)",
         b=4, sq=1, skv=512, h=2, kvh=1, d=128, bq=1, variant="decode")
_catalog("mamba_fwd", _MS,
         "captured mamba_scan forward (A parked, B/C re-streamed per tile)",
         b=1, s=1024, d=512, n=16, variant="fwd")
_catalog("bq_quant", _BQ,
         "captured block_quant quantize (row-tile f32 reads, int8+scale writes)",
         r=512, c=2048, variant="quant")


def capture(name: str) -> CaptureResult:
    """Run (or fetch the cached) capture for one catalog entry."""
    res = _RESULTS.get(name)
    if res is None:
        entry = CAPTURED.get(name)
        if entry is None:
            raise KeyError(
                f"unknown captured kernel {name!r}; catalog: "
                f"{', '.join(CAPTURED)}")
        res = _RESULTS[name] = KernelTraceRecorder(entry.build_geometry()).record()
    return res


def clear_capture_cache() -> None:
    """Drop cached captures (tests re-deriving traces from scratch)."""
    _RESULTS.clear()


def measured_compressibility_of(name: str) -> float:
    from repro.capture.compress import measured_compressibility

    return measured_compressibility(capture(name))


def capture_meta(name: str) -> Dict[str, object]:
    """Source-kernel metadata for one captured workload (``--list``)."""
    entry = CAPTURED[name]
    res = capture(name)
    return {
        "kernel": res.geom.kernel,
        "variant": res.geom.variant,
        "grid": res.geom.grid,
        "operands": tuple(op.name for op in res.geom.operands),
        "n_accesses": res.n_accesses,
        "footprint": res.footprint,
        "config": dict(entry.config),
        "compressibility": measured_compressibility_of(name),
    }


def save_kernel_trace(name: str, path: str) -> CaptureResult:
    """Persist one captured kernel trace through the standard
    ``save_trace`` path — the resulting ``.npz`` replays identically to the
    registered workload (tests/test_capture.py roundtrips it through
    ``register_trace_file``)."""
    from repro.core.sim.trace import save_trace

    res = capture(name)
    save_trace(path, res.trace,
               compressibility=measured_compressibility_of(name))
    return res


def register_captured_kernels(overwrite: bool = False) -> Tuple[str, ...]:
    """Register every catalog entry as a simulator workload.  Called from
    ``repro.core.sim.__init__`` so captured kernels are available out of
    the box; cheap because capture, measurement, and the kernel (jax)
    imports all happen lazily on first use."""
    from repro.core.sim.trace import WORKLOADS, WorkloadSpec, _register, replay_slice

    for name, entry in CAPTURED.items():
        if name in WORKLOADS and not overwrite:
            continue

        def generator(seed: int, footprint: int, n: int,
                      _name: str = name):
            return replay_slice(capture(_name).trace, seed, n)

        def compressibility(_name: str = name,
                            _cache: list = []) -> float:
            if not _cache:
                _cache.append(measured_compressibility_of(_name))
            return _cache[0]

        _register(WorkloadSpec(
            name=name, generator=generator, compressibility=compressibility,
            description=entry.description,
        ), overwrite=overwrite)
    return tuple(CAPTURED)
