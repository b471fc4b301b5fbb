"""Host time of one client op by layer, from the program's sections
(``harness/program_spans.py``): the self time of the layer's sections
inside the traced stretch over the ``client_op`` roots that ended
inside it. ``unspanned`` is the recording thread's CPU time over the
capture less all section self time, over the same ops: what no section
names yet."""

from harness import program_spans


def read(ctx, variant=None):
    red = program_spans.reduce(ctx)
    if red is None:
        return None
    return red.host_ms(variant)
