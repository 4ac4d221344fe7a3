"""Compressed matmuls of one forward: host-clock time of every
``CompressedModel.matmul`` call in the window (kernel and host routes,
operand upload included), per forward, in ms."""


def read(ctx, name):
    calls = ctx.calls or []
    forwards = ctx.win.data.get("forwards", 0)
    if not calls or not forwards:
        return None
    return 1e3 * sum(c["seconds"] for c in calls) / forwards
