"""The NORMALISED value-and-gradient aggregator's share of its roofline, in
a configuration whose fit is one dense fixed-effect solve under a
normalization context: ``aggregator_roofline``'s arithmetic (its reader,
called) under a name of its own. The least seconds the chip could take for the traced fits'
objective evaluations, counted by the SOLVER (ONE read of the design matrix
each, raw as it lies: factors and shifts are ``[width]`` vectors and cost a
read nothing; ``benchmark/roofline.py``, bandwidth-bound), over ALL the
seconds the device was busy in the traced window. It means the same work on
either side of ``ops/pallas_glm.dense_route``'s gate (XLA's two passes read
X twice an evaluation and reach at most half) and cannot pass 100%."""

from benchmark.layer_metrics import aggregator_roofline

LAYER = "aggregators"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    if "normalization" not in run.cfg:
        return None
    return aggregator_roofline.read(run)
