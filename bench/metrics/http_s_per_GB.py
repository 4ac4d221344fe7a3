"""Front door's share of a save: ``client.request`` time less the server's
``engine.save`` time of the same trace, per GB saved."""

from bench.harness.spans import by_trace, total


def read(ctx, name):
    seconds, seen = 0.0, 0
    for roots in by_trace(ctx.roots).values():
        client = [r for r in roots if r.name == "client.request"
                  and r.attrs.get("method") == "POST"]
        if not client:
            continue
        seen += 1
        seconds += (sum(r.elapsed() for r in client)
                    - total(roots, "engine.save"))
    return ctx.per_gb(seconds) if seen else None
