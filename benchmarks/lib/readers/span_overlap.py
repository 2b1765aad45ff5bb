"""The mean, over the measured windows, of how much of one stage lies
inside the window's own latency interval (from the end of its ``start``
stage to the end of the first of its ``end`` stages, by the harness's
clock), in milliseconds. With ``of_previous`` the stage is the window
before's: its ``ship`` still running on the encode worker while this
window's per-row Python runs on the capture thread fights it for the
interpreter lock. The stage's interval ends at the harness's stamp and
is as long as the program wrote. A window whose neighbour is not in the
ring, or that lacks an edge, is left out."""


def read(ctx, stage: str, of_previous: bool = False, start: str = "drain",
         end: tuple[str, ...] = ("encode", "ship")):
    by_seq = {r["seq"]: r for r in ctx.all_rows}
    vals = []
    for r in ctx.rows:
        other = by_seq.get(r["seq"] - 1) if of_previous else r
        last = next((r["ended"][s] for s in end if s in r["ended"]), None)
        if other is None or last is None or start not in r["ended"]:
            continue
        if stage not in other["ended"] or stage not in other["spans"]:
            vals.append(0.0)
            continue
        b = other["ended"][stage]
        a = b - other["spans"][stage][2]
        vals.append(1e3 * max(0.0, min(b, last) - max(a, r["ended"][start])))
    return sum(vals) / len(vals) if vals else None
