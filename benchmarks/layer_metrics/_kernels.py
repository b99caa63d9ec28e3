"""How the attention kernels are named in a device trace.  The splash kernel
is the only Pallas kernel in the tree (ops/attention.py); its calls appear on
the ``XLA Ops`` line as ``splash_mha_fwd_residuals.N`` (forward) and
``splash_mha_dkv_no_residuals.N`` (the fused backward), the names Pallas
gives them (my chip runs, PR 23).  A stable ``jax.named_scope`` on the kernel
is on the list for the ``tracing`` issue; until then this pattern is the one
place that knows the names."""

import re

ATTENTION_OP = re.compile(r"splash_mha|flash_attention|paged_attention", re.I)


def attention_seconds(trace) -> float:
    return sum(s for name, s in trace["op_s"].items() if ATTENTION_OP.search(name))
