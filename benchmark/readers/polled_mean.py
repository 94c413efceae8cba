"""Mean over the window of a field the harness polled from
``/bench/sample`` (live decode slots, KV pool utilisation), times
``scale``."""


def read(ctx, field: str, scale: float = 1.0):
    values = [p[field] for p in ctx.get("polled") or []
              if p.get(field) is not None]
    if not values:
        return None
    return scale * sum(values) / len(values)
