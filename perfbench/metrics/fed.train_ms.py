"""Device milliseconds per round of the vmapped cohort trainer
(``fed/client.py::make_cohort_train``, XLA module ``jit_local_train``),
from the trace."""
import readers


def read(ctx):
    return readers.module_ms_per_round(ctx, ("jit_local_train",))
