"""Programs JAX built (compiled, or loaded from the persistent cache)
between the window's start and the end of its drain; 0 on a warmed run. ``ctx.notes``
keeps their names."""


def read(ctx):
    events = ctx.compiles_in_window
    if events is None:
        return None
    if events:
        ctx.notes["compiles_in_window"] = [
            [name, round(sec, 3)] for _, name, sec in events]
    return float(len(events))
