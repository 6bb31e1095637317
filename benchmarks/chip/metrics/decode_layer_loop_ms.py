"""Device self time of the decode step's layer loop (under ``layers`` in no
block scope: the scan's slices and re-stacks of weights and cache) per
step, in ms.
Read by scope_split.py from the labelled device trace."""

import scope_split

read = scope_split.METRICS["decode_layer_loop_ms"]
