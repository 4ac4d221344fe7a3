"""``<kernel>_roofline``: a kernel's share of its roofline, the least time
of the calls it ran (from each call's logical shape, see
``bench/harness/roofline.py``) over the device time of its launches.
Every metric named ``<kernel>_roofline[.<suffix>]`` without a reader of
its own is read here; ``dequant_matmul`` covers its int4 variant too."""


def read(ctx, name):
    kernel = name.split(".")[0].removesuffix("_roofline")
    calls = [c for c in ctx.calls or ()
             if c["kernel"] == kernel and c["offloaded"]]
    return ctx.roofline_share(
        lambda op: op.startswith(kernel) and op.endswith(" custom-call"),
        calls)
