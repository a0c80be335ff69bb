"""Share of the traced window, in %, in which no op ran on the device;
on several chips, the idlest."""


def read(ctx):
    return 100.0 * max(ctx.idle_shares())
