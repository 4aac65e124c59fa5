"""forward.mfu.phi4flash: model operations of the judge programs inside the trace
over their device time times the bf16 peak (``qnext_scopes.mfu``, which asks
the configuration's family): the WORK THE ANSWER NEEDS, layers 0..17 at every
slot (their products, the scans' recurrence, the sliding layers' attention over
the pairs INSIDE THE BAND) and the full layer's own attention, layers 18..31
and the head at the two positions read.  All 32 layers counted at every slot
would read the split as a share over 100."""

import phi4flash_scopes


def reduce(ctx):
    return phi4flash_scopes.mfu(ctx)
