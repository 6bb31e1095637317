"""Serving cells: a closed loop of static batches through the program's own
compiled entries, on the DaeMon bf16 working copy.

Per batch: ``models/model.prefill`` compiled for the bucket's prompt length;
then one program that takes the first token (argmax) and re-homes the
prompt-sized cache into buffers sized for prompt + generated tokens, as
``launch/serve._grow_cache`` does; then the decode step of
``launch/steps.make_decode_step`` once per further token.  Every token is
fetched to the host as it is made, as a streaming server must, and its
arrival time is taken there.  One prompt length per batch, because the
decode step takes one scalar write position.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

import weights
from repro.core import movement as mv
from repro.launch import steps as steps_lib
from repro.launch.serve import _grow_cache
from repro.models import model as M
from repro.models import nn

HOST_SPANS = ("prompt_to_device", "prefill", "rehome", "decode", "token_to_host")
MIN_SPAN_S = 0.25  # shortest host-clock span a time is read over


def _sds(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree)


def prompts(seed: int, batch_index: int, batch: int, length: int, vocab: int) -> np.ndarray:
    """The prompt token ids of one batch, drawn from the seed."""
    rng = np.random.default_rng([seed, batch_index])
    return rng.integers(0, vocab, (batch, length), dtype=np.int32)


class Cell:
    """The compiled programs of one serving cell (``c``, as ``cell.load``
    gives it), and the working copy of the weights that the cell's
    model-files module makes from the seed."""

    def __init__(self, cfg, c: dict, seed: int,
                 movement: mv.MovementConfig = mv.DAEMON_DEFAULT):
        mf, model, traffic = c["model_files"], c["model"], c["traffic"]
        self.cfg, self.model, self.traffic, self.seed = cfg, model, traffic, seed
        mf.check_layout(model, nn.abstract_params(M.model_specs(cfg)))
        make = jax.jit(lambda k: mv.working_copy(mf.master(model, k), movement))
        self.params = make(weights.seed_key(seed))
        self.programs = {}
        batch, gen = traffic["batch"], traffic["gen_tokens"]
        decode_step = steps_lib.make_decode_step(cfg)

        def prefill(params, batch_in):
            return M.prefill(cfg, params, batch_in)

        for length in traffic["prompt_lens"]:
            def rehome(logits, cache, _total=length + gen):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
                    _grow_cache(cfg, cache, _total)

            tokens = jax.ShapeDtypeStruct((batch, length), jnp.int32)
            pre = jax.jit(prefill).lower(self.params, {"tokens": tokens}).compile()
            logits_s, cache_s = _sds(pre.out_info)
            reh = jax.jit(rehome).lower(logits_s, cache_s).compile()
            tok_s, grown_s = _sds(reh.out_info)
            dec = jax.jit(decode_step, donate_argnums=(1,)).lower(
                self.params, grown_s, tok_s, jax.ShapeDtypeStruct((), jnp.int32)).compile()
            self.programs[length] = (pre, reh, dec)

    def warm_up(self) -> None:
        """Run every program of every bucket once."""
        b = self.traffic["batch"]
        for length, (pre, reh, dec) in self.programs.items():
            logits, cache = pre(self.params, {"tokens": jnp.zeros((b, length), jnp.int32)})
            tok, cache = reh(logits, cache)
            tok, _, cache = dec(self.params, cache, tok, np.int32(length))
            jax.block_until_ready((tok, cache))

    def weight_bytes(self) -> int:
        return sum(x.nbytes for x in jax.tree.leaves(self.params))

    def window(self, seed: int, seconds: float) -> dict:
        """The measured window: static batches, bucket after bucket, from the
        first bucket, until ``seconds`` have passed."""
        t = self.traffic
        batch, gen, lens = t["batch"], t["gen_tokens"], t["prompt_lens"]
        vocab = self.model["vocab_size"]
        batches = []
        with TraceAnnotation("window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            i = 0
            while time.perf_counter() < deadline:
                length = lens[i % len(lens)]
                pre, reh, dec = self.programs[length]
                host_prompt = prompts(seed, i, batch, length, vocab)
                t_start = time.perf_counter()
                with TraceAnnotation("prompt_to_device"):
                    prompt = jax.device_put(host_prompt)
                with TraceAnnotation("prefill"):
                    logits, cache = pre(self.params, {"tokens": prompt})
                with TraceAnnotation("rehome"):
                    tok, cache = reh(logits, cache)
                del logits
                with TraceAnnotation("token_to_host"):
                    toks = [np.asarray(tok)]
                times = [time.perf_counter()]
                pos = length
                while len(toks) < gen and times[-1] < deadline:
                    with TraceAnnotation("decode"):
                        tok, _, cache = dec(self.params, cache, tok, np.int32(pos))
                    with TraceAnnotation("token_to_host"):
                        toks.append(np.asarray(tok))
                    times.append(time.perf_counter())
                    pos += 1
                del cache
                batches.append({"length": length, "prompt": host_prompt, "t_start": t_start,
                                "tokens": np.stack(toks, axis=1), "times": np.array(times)})
                i += 1
            t_end = time.perf_counter()
        return {"t0": t0, "window_s": t_end - t0, "batches": batches, "gen": gen}

    def check_inputs(self, win: dict) -> list:
        """The sample of the window's requests that the reference will follow."""
        return sample(requests(win), self.traffic["check_requests"], self.seed)

    def free(self) -> None:
        del self.params, self.programs


def readings(c: dict, seed: int, chosen: list) -> dict:
    """The numbers that ``correct`` may compare, over the served tokens of
    the sample (only greedy tokens are served): the widest and the mean gap
    by which a served token's reference logit lies below the reference's
    best, and the share of served tokens that are not the reference's best;
    beside them, the mean gap of each prompt length."""
    gaps = logit_gaps(c["model_files"], c["model"], seed, chosen, c["traffic"]["gen_tokens"])
    if not gaps:
        return {}
    every = np.concatenate(gaps)
    out = {"max_logit_gap": float(every.max()), "mean_logit_gap": float(every.mean()),
           "off_best_share": float((every > 0).mean()), "served_compared": int(every.size)}
    for length in sorted({len(p) for p, _ in chosen}):
        mine = np.concatenate([g for (p, _), g in zip(chosen, gaps) if len(p) == length])
        out[f"mean_logit_gap.prompt{length}"] = float(mine.mean())
    return out


def describe(win: dict) -> list:
    """One line per batch of the window: bucket, time to first token, and
    the mean gap between its tokens on the host."""
    out = []
    for b in win["batches"]:
        t = b["times"]
        tpot = (t[-1] - t[0]) / (len(t) - 1) * 1e3 if len(t) > 1 else float("nan")
        out.append(f"batch prompt={b['length']} tokens={b['tokens'].shape[1]} "
                   f"start={b['t_start'] - win['t0']:.3f} ttft_ms={(t[0] - b['t_start']) * 1e3:.1f} "
                   f"tpot_ms={tpot:.2f} end={t[-1] - win['t0']:.3f}")
    return out


def attempted(win: dict) -> int:
    return sum(len(b["prompt"]) for b in win["batches"])


def e2e(win: dict) -> dict:
    """End-to-end numbers of a window, from the host's clock: tokens served
    per second of window, and the 95th percentile over requests of the mean
    gap between a request's tokens, for every request whose tokens in the
    window span a quarter second or more (so one host-clock reading never
    spans less), those still running at the window's end included."""
    tokens = sum(b["tokens"].size for b in win["batches"])
    tpot = []
    for b in win["batches"]:
        t = b["times"]
        if t[-1] - t[0] >= MIN_SPAN_S:
            tpot += [(t[-1] - t[0]) * 1e3 / (len(t) - 1)] * len(b["prompt"])
    out = {"gen_tok_s": tokens / win["window_s"]}
    if tpot:
        out["tpot_p95_ms"] = float(np.percentile(tpot, 95))
    return out


def work(win: dict, batch: int) -> dict:
    """What the window did, for the per-layer metrics: prefills by prompt
    length, and decode steps by write position."""
    prefills, decodes = [], []
    for b in win["batches"]:
        prefills.append(b["length"])
        decodes += [b["length"] + j for j in range(b["tokens"].shape[1] - 1)]
    return {"batch": batch, "prefills": prefills, "decode_positions": decodes}


def requests(win: dict) -> list:
    """Every request of the window, finished or still running at its close:
    (batch index, prompt, tokens served so far)."""
    return [(i, p, t) for i, b in enumerate(win["batches"])
            for p, t in zip(b["prompt"], b["tokens"])]


def sample(reqs: list, n: int, seed: int) -> list:
    """The request with the most tokens (prompt and served), one request of
    every batch of the window, and others up to ``n`` in all, drawn from the
    seed; as (prompt, served tokens)."""
    if not reqs:
        return []
    rng = np.random.default_rng([seed, 0x5EED])
    longest = max(range(len(reqs)), key=lambda i: len(reqs[i][1]) + len(reqs[i][2]))
    picked = [longest]
    for batch in sorted({r[0] for r in reqs} - {reqs[longest][0]}):
        picked.append(int(rng.choice([i for i, r in enumerate(reqs) if r[0] == batch])))
    rest = [i for i in range(len(reqs)) if i not in picked]
    if n > len(picked) and rest:
        picked += [int(i) for i in rng.choice(rest, size=min(n - len(picked), len(rest)),
                                              replace=False)]
    return [reqs[i][1:] for i in sorted(picked)]


def logit_gaps(mf, model: dict, seed: int, chosen: list, gen: int) -> list:
    """Per request of ``chosen``, per served token: how far the reference's
    logit of that token lies below the reference's best, over the prompt
    with its served tokens, by the reference of the model-files module
    ``mf``.  A request still running is read as far as it was served, at the
    shapes of a finished one, so that each bucket compiles one reference
    program."""
    logits_at = mf.reference(model, weights.seed_key(seed))
    gaps = []
    for prompt, served in chosen:
        n = len(served)
        seq = np.zeros(len(prompt) + gen - 1, np.int32)
        seq[:len(prompt) + n - 1] = np.concatenate([prompt, served[:-1]])
        read = len(prompt) - 1 + np.minimum(np.arange(gen), n - 1)
        ref = logits_at(seq, read)[:n]
        best = jnp.max(ref, axis=-1)
        mine = jnp.take_along_axis(ref, jnp.asarray(served, jnp.int32)[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(best - mine))
    del logits_at
    return gaps
