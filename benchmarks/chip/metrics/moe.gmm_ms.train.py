"""Device time per execution of the train-step program (``step`` of
``make_train_step``) spent in the MoE layers' grouped products: the
operations whose HLO instruction is one of XLA's ragged-dot kernels
(``ragged-dot-*``, their tile schedules included), inside the window, over
the window's ``step`` executions. A program without them reads nothing."""
import re

KERNEL = re.compile(r"%?ragged-dot[\w.-]* = ")


def read(rec, ctx):
    if rec.trace is None:
        return None
    _, n = rec.trace.program("step")
    total = sum(t for d in rec.trace.devices for name, t in d.ops.items()
                if KERNEL.match(name))
    if not n or not total:
        return None
    return 1e3 * total / n
