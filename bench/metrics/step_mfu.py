"""The whole step's share of the chip's peak: the operations of every
kernel call the driver's recorder saw (2 x M x K x N of each projection
and the LM head in decode; each distance block the probe offered to the
chip in a save, whichever route ran it), over the traced window, over
the bf16 peak. It bounds a kernel's share even where a later change
takes the kernel off the path."""


def read(ctx, name):
    return ctx.peak_share(list(ctx.calls or ()))
