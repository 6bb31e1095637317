"""Device self time of the decode step's attention core (``attn_core``: KV
write, scores, mask, softmax, weighted sum) per step, in ms.
Read by scope_split.py from the labelled device trace."""

import scope_split

read = scope_split.METRICS["decode_attn_core_ms"]
