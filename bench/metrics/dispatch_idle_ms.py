"""Device idle time inside the program's ``rapid.execute`` spans (put,
dispatch, insert, the wait and read-back), mapped onto the profile's
clock, over the traced slice, per ``rapid.execute`` in it."""
from bench import program_spans as P


def reduce(run):
    split = P.idle_split(run)
    if not split or not split["executes"]:
        return None
    return split["inside"] / split["executes"] * 1e-6
