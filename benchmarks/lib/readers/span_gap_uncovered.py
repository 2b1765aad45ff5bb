"""The mean, over all the measured windows, of the part of the time from
the end of one stage to the end of another (``span_gap``: the harness's
own clock, the same windows, the same far edge) that none of the listed
``stages`` covers: the gap less the duration, as the program wrote it,
of every listed stage that ended inside it. Milliseconds. It says how
much of a latency the program's spans leave unexplained."""


def read(ctx, start: str, end: list[str], stages: list[str]):
    left = []
    for r in ctx.rows:
        ended = r["ended"]
        last = next((ended[s] for s in end if s in ended), None)
        if last is None or start not in ended:
            return None
        covered = sum(r["spans"][s][2] for s in stages
                      if s in r["spans"] and s in ended
                      and ended[start] < ended[s] <= last)
        left.append(1e3 * (last - ended[start] - covered))
    return sum(left) / len(left) if left else None
