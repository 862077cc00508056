"""Device time per window round of the Mamba-2 SSD scan in local training:
the ops of the cohort program ``jit_local_train`` whose scope path holds
``mamba2.ssd`` (``models/mamba2.py`` ``_ssd_chunked``, forward, remat
recompute and backward), as the union of their intervals in the window;
read by op_scopes.py from each op's ``tf_op`` stat. Silent where no op
carries the scope: a program without it, or a model without the scan."""

from op_scopes import scoped_ms

COHORT_PROGRAM = "jit_local_train"
SSD_SCOPE = "mamba2.ssd"


def read(ctx):
    return scoped_ms(ctx, COHORT_PROGRAM, SSD_SCOPE)
