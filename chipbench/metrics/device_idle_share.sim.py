"""Share of the traced window in which no leaf operation ran on the
device (simulator cells): 1 - busy / window, busy being the union of the
device's operation intervals without control flow."""


def read(ctx):
    if ctx.frontend != "sim":
        return None
    share = ctx.trace.idle_share()
    return None if share is None else 100.0 * share
