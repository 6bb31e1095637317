"""Device self time of the decode step's operations in no scope (layout
copies, async copy and slice starts and dones) per step, in ms.
Read by scope_split.py from the labelled device trace."""

import scope_split

read = scope_split.METRICS["decode_unscoped_ms"]
