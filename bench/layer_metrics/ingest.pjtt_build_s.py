"""PJTT build seconds per job: the device time of the programs that build
the parents' join indexes (``_build_sorted``, which sorts each parent's
(key, subject) pairs and folds repeated pairs with ``_compact``, and
``_hash_index`` for the hash strategy), per job."""

PROGRAMS = ("jit__build_sorted", "jit__hash_index")


def read(ctx):
    d = ctx.device
    jobs = ctx.window.get("jobs", 0)
    if d is None or not jobs:
        return None
    found = [d.programs[p] for p in PROGRAMS if p in d.programs]
    if not found:
        return None
    return sum(found) / jobs
