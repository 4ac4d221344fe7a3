"""Share of the traced window in which no operation ran on the device."""


def read(ctx, name):
    return ctx.device_idle()
