"""A stationary population: ``ring`` distinct windows (counts redrawn in
each), replayed in turn, never one twice in a row when ring > 1."""

from .. import generate


def sequence(pop, args: dict, seed: int):
    return generate.PopulationSequence(pop, seed)


def distinct_windows(args: dict, needed: int) -> int:
    return min(needed, int(args["ring"]))


def replay_order(args: dict, needed: int) -> list[int]:
    n = distinct_windows(args, needed)
    return [i % n for i in range(needed)]
