"""How full the engine's batches went out, in %: the lanes that completed
in a batch over ``max_batch`` times the batches (the engine's own
``occupancy`` counter over the run's engine, window and drain)."""


def read(run):
    occ = run["occupancy"]
    return None if occ is None else 100.0 * occ
