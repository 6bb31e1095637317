"""Device self time of the decode step's weight matmuls (``attn_proj``,
``mlp``, ``lm_head``) per step, in ms.
Read by scope_split.py from the labelled device trace."""

import scope_split

read = scope_split.METRICS["decode_matmul_ms"]
