"""setup_s: process start to the first timed step, on the host clock."""


def read(ctx):
    return ctx["out"]["setup_s"]
