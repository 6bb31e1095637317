"""``correct`` comes out true for a sound run and false for each fault the
serving cell can have, driving the rest of a run (set-up, window, check) at
a small size on the CPU with the harness's look for a chip skipped:

- a token altered where it is produced (the decode step), in every batch or
  only in the batch still running when the window closes;
- a decode step that returns its cache unchanged;
- a compile inside the window;
- the control, the program's own int8 working copy.

Each run is held to the limits of the cell it stands for
(``limits/<cell>.json``).  The cell runs on one chip, so there is no
exchange between chips to leave out.
"""
import jax
import jax.numpy as jnp

import cell
import peaks
import run
import serve_loop
from repro.core import movement as mv
from repro.launch import steps as steps_lib
from tiny import model as tiny_model
from tiny import serve_cell

SERVE_LIMITS = cell.load_json(cell.HERE / "limits" / "danube.chat-decode.json")


def _run(c, movement=None, seed=11, seconds=2.0):
    return run.run_cell(c, seed, seconds, False, jax.devices()[:1], peaks.PEAKS["TPU v5 lite"],
                        run.CompileCounter(), movement=movement)


class _Clock:
    """A clock that advances 10 ms per reading, so that a window holds the
    same batches on any machine."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        self.t += 0.01
        return self.t


def _break_decode(monkeypatch, alter):
    """Plants ``alter(cfg, (token, logits, cache), cache_in, pos)`` on the
    decode step's outputs."""
    make = steps_lib.make_decode_step

    def broken(cfg):
        step = make(cfg)

        def decode_step(params, cache, token, pos):
            return alter(cfg, step(params, cache, token, pos), cache, pos)

        return decode_step

    monkeypatch.setattr(steps_lib, "make_decode_step", broken)


def test_sound_serving_run_is_correct():
    out = _run(serve_cell(tiny_model(), SERVE_LIMITS))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and set(out["metrics"]) == {"gen_tok_s", "setup_s"}


def test_altered_token_is_not_correct(monkeypatch):
    _break_decode(monkeypatch, lambda cfg, o, _c, _p: ((o[0] + 1) % cfg.vocab_size, *o[1:]))
    out = _run(serve_cell(tiny_model(), SERVE_LIMITS))
    assert not out["correct"], out["checks"]


def test_running_batch_is_compared(monkeypatch):
    """The window closes while the batch of the longest prompts is still
    decoding (8 tokens a batch, 10 ms a clock reading, a 0.15 s window): a
    sound run compares both buckets and is correct, and a token altered
    only in that running batch is caught."""
    monkeypatch.setattr(serve_loop, "time", _Clock())
    c = serve_cell(tiny_model(), SERVE_LIMITS, prompt_lens=(8, 32), check_requests=2)
    out = _run(c, seconds=0.15)
    assert out["correct"], out["checks"]
    assert {"mean_logit_gap.prompt8", "mean_logit_gap.prompt32"} <= set(out["readings"])
    assert out["attempted"] == 4 and 8 < out["readings"]["served_compared"] < 16

    def late(cfg, o, _c, pos):
        return (jnp.where(pos >= 32, (o[0] + 1) % cfg.vocab_size, o[0]), *o[1:])

    _break_decode(monkeypatch, late)
    monkeypatch.setattr(serve_loop, "time", _Clock())
    out = _run(c, seconds=0.15)
    assert not out["correct"], out["checks"]
    assert out["readings"]["mean_logit_gap.prompt8"] <= SERVE_LIMITS["mean_logit_gap"]["limit"]


def test_cache_left_unchanged_is_not_correct(monkeypatch):
    _break_decode(monkeypatch, lambda cfg, o, cache_in, _p: (o[0], o[1], cache_in))
    out = _run(serve_cell(tiny_model(), SERVE_LIMITS))
    assert not out["correct"], out["checks"]


def test_compile_in_window_is_not_correct(monkeypatch):
    window = serve_loop.Cell.window

    def compiling(self, seed, seconds):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 3)))  # a new program in the window
        return window(self, seed, seconds)

    monkeypatch.setattr(serve_loop.Cell, "window", compiling)
    out = _run(serve_cell(tiny_model(), SERVE_LIMITS))
    assert not out["correct"] and out["checks"]["compiles_in_window"]["value"] > 0


def test_control_int8_working_copy_is_not_correct(monkeypatch):
    """The control, the program's own int8 working copy in place of bf16,
    at a width where its quantization applies (128-multiples) and with
    danube's vocabulary, read as on the chip over three seeds: every
    control run comes out not correct against the cell's limits file,
    every sound run correct, and each control reading of the compared
    number is at least three times the sound runs' largest."""
    conf = tiny_model(hidden=256, heads=4, kv=2, head_dim=64, ff=768, vocab=32000, window=256,
                      layers=6)
    c = serve_cell(conf, SERVE_LIMITS, batch=8, prompt_lens=(64,), gen=64, check_requests=64)
    monkeypatch.setattr(serve_loop, "time", _Clock())
    sound, control = [], []
    for seed in range(3):
        out = _run(c, seed=seed)
        assert out["correct"], out["checks"]
        sound.append(out["readings"]["mean_logit_gap"])
        out = _run(c, movement=mv.DAEMON_AGGRESSIVE, seed=seed)
        assert not out["correct"], out["checks"]
        control.append(out["readings"]["mean_logit_gap"])
    assert min(control) >= 3 * max(sound), (sound, control)
