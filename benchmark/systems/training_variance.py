"""The training system under test for a configuration with a ``variance``:
``benchmark/systems/training.py``'s ``GameEstimator`` with
``variance_computation_type`` set from the configuration, the way a
training job's ``--variance-computation-type`` sets it, and the published
variances read back beside the means. Frames and fitted means go through
``training``'s functions."""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark.systems import training


def estimator(cfg: dict, variance: str = "", **kw):
    """``training.estimator``'s ``GameEstimator`` publishing the
    configuration's ``variance`` (NONE | SIMPLE | FULL): the public
    attribute its constructor fills (``variance_computation_type``), read
    when a fit prepares the frame. ``variance`` and ``kw``
    (``feature_dtype``) are there for the runs that show a lesser job
    failing ``correct``."""
    from photon_tpu.types import VarianceComputationType

    est = training.estimator(cfg, **kw)
    est.variance_computation_type = VarianceComputationType[
        variance or cfg["variance"]]
    return est


def published(cfg: dict, model) -> Dict[str, object]:
    """What a fit publishes, as device arrays by name: ``<id>.means`` of
    every coordinate and ``<id>.variances`` of every fixed effect whose
    model carries them."""
    out = {f"{c['id']}.means": a for c, a in zip(
        cfg["coordinates"], training.coefficient_arrays(cfg, model))}
    for c in cfg["coordinates"]:
        if c["kind"] == "fixed":
            v = model[c["id"]].model.coefficients.variances
            if v is not None:
                out[f"{c['id']}.variances"] = v
    return out


def wait(cfg: dict, model) -> list:
    """Block until everything the fit publishes is on the device, means
    AND variances; the names of what was waited on."""
    import jax

    arrays = published(cfg, model)
    jax.block_until_ready(list(arrays.values()))
    return sorted(arrays)


def variance_tables(cfg: dict, model) -> Dict[str, np.ndarray]:
    """The fixed effects' published variances, ``{coordinate id: [width]}``
    float32, in ``training.model_tables``'s layout; a coordinate whose
    model carries none is left out."""
    return {name[:-len(".variances")]: np.asarray(a, np.float32)
            for name, a in published(cfg, model).items()
            if name.endswith(".variances")}


def variances_counted() -> float:
    """The program's own count of FULL variance computations so far, over
    every coordinate: the always-on counter ``variance.computed{coordinate,
    type="FULL"}``, one tick an update that computed them. 0 on a program
    from before it counted."""
    from photon_tpu.obs.metrics import registry

    return sum(value for labels, value in registry.series("variance.computed")
               if labels.get("type") == "FULL")
