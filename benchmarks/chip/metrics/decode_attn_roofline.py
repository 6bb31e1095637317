"""Share of the decode step's attention-core time that the roofline says its
steps need, from the counts of the cell's model-files module.
Read by scope_split.py from the labelled device trace."""

import scope_split

read = scope_split.METRICS["decode_attn_roofline"]
