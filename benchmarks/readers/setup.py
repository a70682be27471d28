"""Process start to the window's opening, on the parent's clock."""


def read(args, ctx):
    return ctx.setup_s
