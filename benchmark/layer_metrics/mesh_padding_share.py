"""Share of the random effects' slots over the mesh that are the mesh's
padding: the zero-weight entities ``parallel/mesh.pad_entities`` adds to
each size bucket so that its entities divide over the chips, each a whole
row of the bucket's slots, over every slot of every bucket. The program's
counter ``mesh.entity_slots{coordinate, kind=real|pad}``, ticked once when
the estimator places its datasets; a bucket that holds one heavy entity
(the top movie's) pads it to a row on every chip, which every chip then
solves in lockstep. ``None`` where nothing ticked it (no mesh, or a program
without the counter)."""

LAYER = "cd_solver"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"

COUNTER = "mesh.entity_slots{"


def read(run):
    from photon_tpu.obs.metrics import registry

    slots = {"real": 0.0, "pad": 0.0}
    for key, value in registry.snapshot()["counters"].items():
        if key.startswith(COUNTER):
            slots["pad" if 'kind="pad"' in key else "real"] += value
    total = slots["real"] + slots["pad"]
    return 100.0 * slots["pad"] / total if total else None
