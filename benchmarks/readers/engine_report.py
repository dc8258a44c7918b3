"""What the engine says of its own build (pool pages, buckets).

args: ``field`` of ``harness.system.engine_report``."""


def read(ctx, field):
    v = (ctx.engine_report or {}).get(field)
    return None if v is None else float(v)
