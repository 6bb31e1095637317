"""The plain f32 reference against the program at a small size on the CPU:
prefill and decode through the (ring) cache give the reference's logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cell
import weights
from repro.core import movement as mv
from repro.launch import steps as steps_lib
from repro.launch.serve import _grow_cache
from repro.models import model as M
from tiny import model as tiny_model

# bf16 activations and weights in the program against f32 in the reference,
# on logits of unit scale
LOGIT_TOL = 0.08


@pytest.mark.parametrize("tied,window,prompt", [(False, 16, 32), (False, 16, 4), (True, 0, 24)],
                         ids=["ring", "inside-window", "tied-full"])
def test_serving_path_matches_reference(tied, window, prompt):
    conf = tiny_model(window=window, tied=tied, kv=4 if tied else 2)
    m, cfg, mf = cell.model_sizes(conf), cell.model_config(conf), cell.model_files("dense")
    master = mf.master(m, weights.seed_key(3))
    params = mv.working_copy(master, mv.DAEMON_DEFAULT)
    gen = 12
    rng = np.random.default_rng(0)
    toks = rng.integers(0, m["vocab_size"], (2, prompt + gen), dtype=np.int32)
    logits, cache = M.prefill(cfg, params, {"tokens": jnp.asarray(toks[:, :prompt])})
    cache = _grow_cache(cfg, cache, prompt + gen)
    got = [logits]
    step = jax.jit(steps_lib.make_decode_step(cfg))
    for i in range(gen - 1):
        _, lg, cache = step(params, cache, jnp.asarray(toks[:, prompt + i]), jnp.int32(prompt + i))
        got.append(lg)
    got = np.stack(got, axis=1)
    read = np.arange(prompt - 1, prompt + gen - 1)
    logits_at = mf.reference(m, weights.seed_key(3))
    for b in range(2):
        ref = np.asarray(logits_at(toks[b, :-1], read))
        assert np.abs(got[b] - ref).max() < LOGIT_TOL
