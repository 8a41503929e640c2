"""What the lane readers share: ``photon_tpu.obs.solver.lane_counts()``,
the buffered random-effect updates' ``lane_counts()`` a coordinate (one
entry a (coordinate, sweep), a later fit's replacing an earlier fit's: so
ONE fit's, the last of the window; every fit repeats its counts, or the run
is not ``correct``), summed over the coordinates. A size bucket's
per-entity solves run as ONE vmapped loop that trips until the bucket's
slowest entity is done: ``sum`` adds the entities' own iteration counts,
``trips`` the buckets' largest counts, ``capacity`` entities of a bucket x
its largest count. Nothing is read inside the window: the updates' arrays
cross to the host here. A program from before it had the function, or a run
with telemetry off, has none."""


def lane_iterations(stat):
    from photon_tpu.obs import solver

    counts = getattr(solver, "lane_counts", dict)()
    return sum(c[stat] for c in counts.values()) if counts else None
