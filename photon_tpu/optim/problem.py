"""Optimization problems: config + objective + solver, with variances.

Reference: photon-api optimization/GeneralizedLinearOptimizationProblem
.scala, DistributedOptimizationProblem.scala:46 (run :177, runWithSampling
:159, computeVariances :82-100, updateRegularizationWeight),
SingleNodeOptimizationProblem.scala:40, OptimizerConfig.scala:28,
CoordinateOptimizationConfiguration.scala:30,48.

TPU re-design: ONE problem class serves both the reference's Distributed
(RDD) and SingleNode (Iterable) realizations — the same jitted solve runs
over a mesh-sharded batch (psum reductions) or vmapped over entity blocks.
Regularization weights are traced arguments, so a reg-path sweep reuses a
single compilation (the warm-start chain of ModelTraining.scala:134-147).
"""

from __future__ import annotations

import dataclasses
import functools  # noqa: F401 (unused since PR 40; a line taken out here moves ``solve`` and re-keys every kernel-bearing program, PERF.md §6 PR 33)
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from photon_tpu.data.dataset import DataBatch
from photon_tpu.function.objective import (
    GLMObjective,
    Hyper,
    NoRegularization,
    RegularizationContext,
)
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.ops.normalization import NormalizationContext, no_normalization
from photon_tpu.optim import lbfgs, owlqn, tron
from photon_tpu.optim.base import SolverConfig, SolverResult, jit_donating
from photon_tpu.types import OptimizerType, TaskType, VarianceComputationType
from photon_tpu.utils import jitcache

Array = jax.Array


class SweptSolve(NamedTuple):
    """Output of :meth:`GlmOptimizationProblem.solve_swept`: one model per
    grid lane, plus the stacked device views."""

    models: List[GeneralizedLinearModel]   # per-lane, original space
    stacked: SolverResult                  # every field has a [K] lane axis
    coefs: Array                           # [K, d] original-space stack

    @property
    def results(self) -> List[SolverResult]:
        """Per-lane views of ``stacked``, sliced when asked: K x 7 eager
        device programs, which no caller inside a fit pays for."""
        from photon_tpu.optim import batched
        return batched.split_lanes(self.stacked)


def _validate_direct(task, opt: "OptimizerConfig", regularization) -> None:
    """DIRECT's contract is the EXACT minimizer; reject every config it
    cannot solve exactly (shared by the fixed- and random-effect paths)."""
    if task != TaskType.LINEAR_REGRESSION:
        raise ValueError(
            "OptimizerType.DIRECT is exact only for the quadratic squared "
            f"loss (LINEAR_REGRESSION); use NEWTON for logistic/Poisson or "
            f"LBFGS/TRON for {task}")
    if opt.lower_bounds is not None or opt.upper_bounds is not None:
        raise ValueError("DIRECT does not support box constraints")
    if regularization.l1_weight(1.0) != 0.0:
        raise ValueError(
            "DIRECT solves the L2/unregularized normal equations exactly; "
            "L1/elastic-net needs OWLQN")


def _validate_newton(task, opt: "OptimizerConfig", regularization) -> None:
    """NEWTON needs second derivatives and a smooth objective (shared by
    the fixed- and random-effect paths)."""
    from photon_tpu.ops.losses import loss_for_task
    if not loss_for_task(task).has_hessian:
        raise ValueError(
            f"OptimizerType.NEWTON needs a twice-differentiable loss; "
            f"{task} has no Hessian — use LBFGS")
    if opt.lower_bounds is not None or opt.upper_bounds is not None:
        raise ValueError("NEWTON does not support box constraints; "
                         "use LBFGSB")
    if regularization.l1_weight(1.0) != 0.0:
        raise ValueError("NEWTON needs a smooth objective; L1/elastic-net "
                         "needs OWLQN")


def solver_cache_key(opt: "OptimizerConfig") -> tuple:
    """Everything in an OptimizerConfig that shapes a solver's trace."""
    return (opt.optimizer_type, opt.max_iterations, opt.tolerance,
            opt.num_corrections, opt.max_cg_iterations, opt.track_states,
            opt.explicit_hessian,
            jitcache.array_token(opt.lower_bounds),
            jitcache.array_token(opt.upper_bounds))


def norm_cache_key(norm) -> tuple:
    return (jitcache.array_token(norm.factors),
            jitcache.array_token(norm.shifts))


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Reference: OptimizerConfig.scala:28 (+ per-solver defaults)."""

    optimizer_type: OptimizerType = OptimizerType.LBFGS
    max_iterations: int = 100
    tolerance: float = 1e-7
    num_corrections: int = 10
    max_cg_iterations: int = 20
    lower_bounds: Optional[jax.Array] = None
    upper_bounds: Optional[jax.Array] = None
    # per-iteration (loss, ||g||) ring size; 0 = no tracking
    track_states: int = 0
    # TRON Hessian strategy: True = build the d x d Gauss-Newton matrix once
    # per outer iteration (one MXU GEMM; CG steps become O(d^2)); False =
    # matrix-free Hv with per-iteration curvature weights; None = auto
    # (``tron_explicit_hessian``: dense features and, on a TPU, dim < 256)
    explicit_hessian: Optional[bool] = None

    def solver_config(self) -> SolverConfig:
        return SolverConfig(
            max_iterations=self.max_iterations,
            tolerance=self.tolerance,
            num_corrections=self.num_corrections,
            max_cg_iterations=self.max_cg_iterations,
            lower_bounds=self.lower_bounds,
            upper_bounds=self.upper_bounds,
            track_states=self.track_states,
        )


@dataclasses.dataclass(frozen=True)
class GLMOptimizationConfiguration:
    """Per-coordinate optimization config (reference:
    CoordinateOptimizationConfiguration.scala:30,48)."""

    optimizer: OptimizerConfig = OptimizerConfig()
    regularization: RegularizationContext = NoRegularization
    regularization_weight: float = 0.0
    down_sampling_rate: float = 1.0


class GlmOptimizationProblem:
    """Task + config + normalization -> a reusable, jit-cached GLM solve.

    ``run`` maps to Optimizer.optimize over the whole batch; the reg weight
    is dynamic so ``update_regularization_weight`` (reference reg-path
    support) is free.

    Model space contract: the OPTIMIZER runs in transformed (normalized)
    coefficient space — that is the conditioning win — but every model this
    class accepts (warm starts) and returns lives in ORIGINAL feature
    space, converted at this boundary via the margin-invariant maps
    (reference: NormalizationContext.scala:80-126). Published models can
    therefore always be scored as theta.x against raw features.
    """

    def __init__(
        self,
        task: TaskType,
        config: GLMOptimizationConfiguration = GLMOptimizationConfiguration(),
        norm: NormalizationContext = no_normalization(),
        intercept_index: Optional[int] = None,
    ):
        if norm.shifts is not None and intercept_index is None:
            # a shift moves margins by a constant; only an intercept can
            # absorb it (reference: NormalizationContext requires an
            # intercept for shift-ful normalization types)
            raise ValueError(
                "normalization with shifts (STANDARDIZATION) requires an "
                "intercept feature; pass intercept_index")
        self.task = task
        self.config = config
        self.intercept_index = intercept_index
        self.objective = GLMObjective(loss_for_task(task), norm)
        # variances are reported for the PUBLISHED (original-space) model,
        # so curvature is evaluated with the unnormalized objective
        self._var_objective = (
            self.objective if norm.is_identity
            else GLMObjective(loss_for_task(task)))

    # -- solving ------------------------------------------------------------

    @property
    def _solve_fn(self):
        """Default solve (non-mesh callers / HLO inspection in tests)."""
        return self._solve_fn_for(True)

    def _solve_fn_for(self, kernel_ok: bool):
        opt = self.config.optimizer
        solver_cfg = opt.solver_config()
        obj = self.objective

        if opt.optimizer_type == OptimizerType.DIRECT:
            _validate_direct(self.task, opt, self.config.regularization)
        if opt.optimizer_type == OptimizerType.NEWTON:
            _validate_newton(self.task, opt, self.config.regularization)

        def build():
            def solve(x0: Array, batch: DataBatch, l2: Array, l1: Array) -> SolverResult:
                hyper = Hyper(l2_weight=l2)
                vg = lambda c: obj.value_and_gradient(c, batch, hyper)
                if opt.optimizer_type == OptimizerType.DIRECT:
                    from photon_tpu.optim import direct
                    return direct.minimize(
                        vg, lambda c: obj.hessian_matrix(c, batch, hyper), x0)
                if opt.optimizer_type == OptimizerType.NEWTON:
                    # explicit Hessian via the curvature-weights split: one
                    # weighted-Gram MXU contraction per outer iteration
                    # (same operator TRON's explicit gate builds)
                    from photon_tpu.ops.features import ModelShardedSparse
                    if isinstance(batch.features, ModelShardedSparse):
                        raise ValueError(
                            "NEWTON builds an explicit d x d Hessian, "
                            "which contradicts model-axis sharding of a "
                            "sparse theta; use LBFGS or TRON (matrix-"
                            "free) for this coordinate")
                    from photon_tpu.optim import newton
                    dim = x0.shape[0]
                    if opt.explicit_hessian is not True and dim > 8192:
                        # 8192^2 f32 = 256 MB per Hessian; beyond that the
                        # explicit build stops being an MXU bargain even
                        # on chip — NEWTON has no matrix-free mode, so
                        # refuse instead of OOMing (trace-time check:
                        # shapes are static under jit)
                        raise ValueError(
                            f"NEWTON builds an explicit [{dim}, {dim}] "
                            f"Hessian; use TRON (matrix-free) above "
                            f"d=8192, or set explicit_hessian=True to "
                            f"override")
                    return newton.minimize(
                        vg,
                        lambda c: obj.hessian_matrix_from_weights(
                            obj.hessian_weights(c, batch), dim, batch, hyper),
                        x0, config=solver_cfg)
                if opt.optimizer_type == OptimizerType.OWLQN:
                    return owlqn.minimize(vg, x0, l1_weight=l1, config=solver_cfg)
                if opt.optimizer_type == OptimizerType.TRON:
                    # the operator from the curvature weights each
                    # evaluation hands back: the explicit d x d Gauss-Newton
                    # matrix (one MXU contraction an accepted step, no pass
                    # over X a CG step) or the weights, matrix-free (a read
                    # of X a step, two off the kernel), by tron_explicit_hessian
                    from photon_tpu.ops.features import (
                        ModelShardedSparse,
                        SparseFeatures,
                    )
                    dim = x0.shape[0]
                    explicit = opt.explicit_hessian
                    if explicit is None:
                        explicit = tron_explicit_hessian(
                            not isinstance(batch.features, (
                                SparseFeatures, ModelShardedSparse)), dim)
                    # ticked at TRACE time: once a traced solve, with the
                    # operator the solve was traced with
                    from photon_tpu.obs.metrics import registry
                    registry.counter(
                        "kernels.tron_hessian",
                        path="explicit" if explicit else "matrix_free").inc()
                    if explicit:
                        hs = lambda d2: obj.hessian_matrix_from_weights(
                            d2, dim, batch, hyper)
                        ha = lambda h, v: h @ v
                    else:
                        hs, ha = None, lambda d2, v: (
                            obj.hessian_vector_from_weights(d2, v, batch, hyper))
                    vgw = lambda c: obj.value_gradient_and_weights(c, batch, hyper)
                    return tron.minimize(vgw, None, x0, config=solver_cfg,
                                         hess_setup=hs, hess_apply=ha)
                from photon_tpu.ops.features import ModelShardedSparse
                if (isinstance(batch.features, ModelShardedSparse)
                        and batch.features.csc_ptr is not None
                        and opt.lower_bounds is None
                        and opt.upper_bounds is None):
                    # margin-resident directional L-BFGS: on the sharded
                    # path every feature pass is the wallclock, so the
                    # solve keeps margins resident and pays exactly one
                    # matvec + one rmatvec per iteration instead of one
                    # full evaluation per line-search trial. Gated on the
                    # CSC plan: a plan-less ModelShardedSparse is the
                    # legacy compatibility layout, and gets the legacy
                    # (classic line-search) solver with the scatter kernels
                    dp = obj.directional_problem(batch, hyper)
                    return lbfgs.minimize_directional(dp, x0,
                                                      config=solver_cfg)
                return lbfgs.minimize(vg, x0, config=solver_cfg)

            # donate x0 into the while-loop carry (accelerator backends
            # only — see optim/base.jit_donating)
            return jit_donating(solve, donate_argnums=(0,))

        # share the compiled solve across problem instances with identical
        # trace-shaping state (re-fits, sweep candidates, fresh
        # estimators). kernel_ok is trace-shaping too: a mesh solve is
        # traced inside ``pallas_glm.disabled()`` and a single-device
        # solve is not, and the two must not share a trace (the fused
        # kernel carries no sharding annotations).
        key = ("glm_solve", self.task, solver_cache_key(opt),
               norm_cache_key(self.objective.norm), kernel_ok)
        return jitcache.get_or_build(key, build)

    def run(
        self,
        batch: DataBatch,
        initial: Optional[Array] = None,
        dim: Optional[int] = None,
        dtype=None,
        regularization_weight: Optional[float] = None,
        mesh=None,
        pallas_ok: Optional[bool] = None,
    ) -> Tuple[GeneralizedLinearModel, SolverResult]:
        """Solve and return (model, solver stats). Variances are computed
        separately via ``compute_variances`` (reference behavior: variances
        only on the final model).

        With ``mesh``, the batch is sample-sharded over the mesh's data
        axis and the coefficients replicated before the jitted solve — the
        whole optimize loop then runs as ONE SPMD program whose gradient
        reductions are all-reduces over ICI (the treeAggregate + broadcast
        replacement, SURVEY §5.8)."""
        norm = self.objective.norm
        if self.config.optimizer.optimizer_type == OptimizerType.SDCA:
            import numpy as np
            if mesh is not None:
                raise ValueError(
                    "SDCA over a resident batch does not take a mesh — "
                    "build a meshed ChunkLoader and call run_streamed")
            if initial is not None and bool(np.any(np.asarray(initial) != 0)):
                raise ValueError(
                    "SDCA cannot warm-start from nonzero coefficients "
                    "(no dual preimage for an arbitrary w); start from "
                    "zeros or use LBFGS for warm-started re-fits")
            if dim is None and initial is not None:
                dim = int(np.shape(initial)[0])
            return self.run_sdca_resident(
                batch, dim=dim, dtype=dtype,
                regularization_weight=regularization_weight)
        if dtype is None:
            # match the batch: a float32 x0 against float64 data would
            # promote mid-solve and break the while_loop carry contract
            dtype = batch.labels.dtype
        if initial is None:
            assert dim is not None, "need dim when no initial coefficients"
            initial = jnp.zeros((dim,), dtype)
        elif not norm.is_identity:
            # warm starts arrive in original space; optimize in transformed
            initial = norm.model_to_transformed_space(
                jnp.asarray(initial), self.intercept_index)
        else:
            initial = jnp.asarray(initial)
            if mesh is None and jax.default_backend() != "cpu":
                # the jitted solve donates x0; this is the only path where
                # the caller's own array would reach the donated position
                # unwrapped (coordinate descent reuses the previous model
                # as the warm start across outer iterations)
                initial = initial.copy()
        if mesh is not None:
            from photon_tpu.parallel import mesh as M
            batch = M.shard_batch(batch, mesh)
            initial = M.replicate(initial, mesh)
        lam = (self.config.regularization_weight
               if regularization_weight is None else regularization_weight)
        l2 = jnp.asarray(self.config.regularization.l2_weight(lam), initial.dtype)
        l1 = jnp.asarray(self.config.regularization.l1_weight(lam), initial.dtype)
        # mesh here OR a caller-declared sharded batch (FixedEffect
        # Coordinate pre-shards at construction and passes pallas_ok=False)
        kernel_ok = mesh is None and pallas_ok is not False
        solve = self._solve_fn_for(kernel_ok)
        if kernel_ok:
            result = solve(initial, batch, l2, l1)
        else:
            # the fused kernel has no sharding annotations: under a mesh
            # it would force replication of X or fail at lowering, so the
            # SPMD solve traces with the kernel hard-disabled
            from photon_tpu.ops import pallas_glm
            with pallas_glm.disabled():
                result = solve(initial, batch, l2, l1)
        coef = result.coef
        if not norm.is_identity:
            coef = norm.transformed_space_to_model(coef, self.intercept_index)
        model = GeneralizedLinearModel(Coefficients(coef), self.task)
        return model, result

    # -- lane-batched sweeps (optim/batched) --------------------------------

    def _swept_solve_fn(self, mesh):
        opt = self.config.optimizer
        if opt.optimizer_type not in (OptimizerType.LBFGS,
                                      OptimizerType.OWLQN):
            raise ValueError(
                f"solve_swept supports LBFGS/OWLQN only, not "
                f"{opt.optimizer_type} (second-order solvers have no "
                f"vmappable lax-level batching rule for the lane stack)")
        from photon_tpu.optim import batched
        solver_cfg = opt.solver_config()
        obj = self.objective
        use_owlqn = opt.optimizer_type == OptimizerType.OWLQN

        def build():
            if mesh is None:
                def solve(x0_lanes: Array, batch: DataBatch,
                          l2: Array, l1: Array) -> SolverResult:
                    vg = lambda c, hyper: obj.value_and_gradient(
                        c, batch, hyper)
                    return batched.minimize_lanes(
                        vg, x0_lanes, l2=l2, l1=l1, config=solver_cfg,
                        use_owlqn=use_owlqn)
                return jit_donating(solve, donate_argnums=(0,))

            def solve(x0_lanes: Array, batch: DataBatch,
                      l2: Array, l1: Array) -> SolverResult:
                return batched.minimize_lanes_meshed(
                    obj, batch, x0_lanes, l2=l2, l1=l1, mesh=mesh,
                    config=solver_cfg, use_owlqn=use_owlqn)
            return jax.jit(solve)

        key = ("glm_solve_swept", self.task, solver_cache_key(opt),
               norm_cache_key(self.objective.norm),
               None if mesh is None else jitcache.array_token(mesh))
        return jitcache.get_or_build(key, build)

    def solve_swept(
        self,
        batch: DataBatch,
        lambdas,
        initial: Optional[Array] = None,
        initial_lanes: Optional[Array] = None,
        dim: Optional[int] = None,
        dtype=None,
        mesh=None,
    ) -> "SweptSolve":
        """Fit the whole regularization grid ``lambdas`` as ONE compiled
        lane-batched program (optim/batched.minimize_lanes).

        Same model-space contract as ``run``, per lane: warm starts
        (``initial`` shared, or ``initial_lanes [K, d]`` per lane) arrive
        in original space and the returned models live in original
        space. Weights are validated typed at entry
        (:class:`~photon_tpu.optim.batched.SweepWeightError`), never
        inside the compiled solve. A singleton grid compiles the same
        loop structure as the scalar solver ("any over one lane" is the
        scalar cond), so K=1 matches ``run``'s iteration count with
        coefficient parity at trace precision.
        """
        from photon_tpu.optim import batched
        from photon_tpu.ops.features import ModelShardedSparse
        if isinstance(batch.features, ModelShardedSparse):
            raise ValueError(
                "solve_swept does not support model-sharded features: K "
                "lanes hold K full coefficient vectors, which contradicts "
                "a theta range-sharded over the model axis")
        lams = batched.validate_lane_weights(lambdas, name="solve_swept grid")
        k = int(lams.shape[0])
        norm = self.objective.norm
        if dtype is None:
            dtype = batch.labels.dtype
        to_opt_space = (lambda c: c) if norm.is_identity else (
            lambda c: norm.model_to_transformed_space(c, self.intercept_index))
        if initial_lanes is not None:
            x0 = jnp.asarray(initial_lanes, dtype)
            if x0.ndim != 2 or x0.shape[0] != k:
                raise ValueError(
                    f"initial_lanes must be [K={k}, d], got {x0.shape}")
            x0 = jax.vmap(to_opt_space)(x0)
        elif initial is not None:
            init = to_opt_space(jnp.asarray(initial, dtype))
            x0 = jnp.broadcast_to(init, (k,) + init.shape) + 0
        else:
            assert dim is not None, "need dim when no initial coefficients"
            x0 = jnp.zeros((k, dim), dtype)
        if mesh is not None:
            from photon_tpu.optim import hier
            from photon_tpu.parallel import mesh as M
            sample_axes = hier._sample_axes(mesh)
            batch = M.shard_batch(
                batch, mesh,
                axis=sample_axes if len(sample_axes) > 1 else sample_axes[0])
            x0 = M.replicate(x0, mesh)
        reg = self.config.regularization
        l2 = jnp.asarray([reg.l2_weight(l) for l in lams], dtype)
        l1 = jnp.asarray([reg.l1_weight(l) for l in lams], dtype)
        solve = self._swept_solve_fn(mesh)
        # the fused kernel has no batching rule for the lane stack;
        # the swept program always traces with it hard-disabled
        from photon_tpu.ops import pallas_glm
        with pallas_glm.disabled():
            stacked = solve(x0, batch, l2, l1)
        coefs = stacked.coef
        if not norm.is_identity:
            coefs = jax.vmap(lambda c: norm.transformed_space_to_model(
                c, self.intercept_index))(coefs)
        # iterating a device array unstacks it in ONE program (an index a
        # lane is two eager programs a lane)
        models = [GeneralizedLinearModel(Coefficients(c), self.task)
                  for c in coefs]
        return SweptSolve(models=models, stacked=stacked, coefs=coefs)

    def run_streamed(
        self,
        loader,
        initial: Optional[Array] = None,
        dim: Optional[int] = None,
        dtype=None,
        regularization_weight: Optional[float] = None,
        checkpoint_path: Optional[str] = None,
        checkpoint_every_chunks: int = 0,
        sdca_config=None,
    ) -> Tuple[GeneralizedLinearModel, SolverResult]:
        """Out-of-core solve: same contract as ``run`` but the data is a
        ``data.streaming.ChunkLoader`` instead of a resident batch — the
        objective is accumulated chunk-by-chunk with double-buffered
        host->device transfer, so the dataset never needs to fit in HBM.

        Only first-order solvers stream (LBFGS; OWLQN when the
        regularization has an L1 part; SDCA for one-storage-pass-per-epoch
        stochastic training — optim/sdca.py): second-order solvers would
        need a streamed pass per Hessian application. The mesh (if any)
        comes from the loader. ``checkpoint_path`` enables the
        chunk-cursor checkpoint for bitwise mid-epoch resume after
        preemption. ``sdca_config`` (an :class:`optim.sdca.SdcaConfig`)
        overrides the default OptimizerConfig mapping
        (max_iterations -> max_epochs, tolerance -> relative
        gap_tolerance) for the SDCA arm."""
        from photon_tpu.optim import streaming

        opt = self.config.optimizer
        if opt.optimizer_type == OptimizerType.SDCA:
            return self._run_sdca(
                loader, initial=initial, dim=dim, dtype=dtype,
                regularization_weight=regularization_weight,
                checkpoint_path=checkpoint_path,
                checkpoint_every_chunks=checkpoint_every_chunks,
                sdca_config=sdca_config)
        if opt.optimizer_type not in (OptimizerType.LBFGS,
                                      OptimizerType.OWLQN):
            raise ValueError(
                f"streamed training supports LBFGS/OWLQN/SDCA only, not "
                f"{opt.optimizer_type} (second-order solvers need a full "
                f"pass per Hessian application)")
        norm = self.objective.norm
        if dtype is None:
            dtype = loader.dtype
        d = int(dim if dim is not None else loader.source.dim)
        if initial is None:
            initial = jnp.zeros((d,), dtype)
        elif not norm.is_identity:
            initial = norm.model_to_transformed_space(
                jnp.asarray(initial), self.intercept_index)
        lam = (self.config.regularization_weight
               if regularization_weight is None else regularization_weight)
        problem = streaming.StreamedProblem(
            self.objective, loader,
            l2_weight=self.config.regularization.l2_weight(lam),
            dim=d, dtype=dtype)
        result = streaming.minimize_streamed(
            problem, jnp.asarray(initial, dtype),
            config=opt.solver_config(),
            l1_weight=self.config.regularization.l1_weight(lam),
            checkpoint_path=checkpoint_path,
            checkpoint_every_chunks=checkpoint_every_chunks)
        coef = result.coef
        if not norm.is_identity:
            coef = norm.transformed_space_to_model(coef, self.intercept_index)
        model = GeneralizedLinearModel(Coefficients(coef), self.task)
        return model, result

    def _run_sdca(
        self,
        loader,
        *,
        initial,
        dim,
        dtype,
        regularization_weight,
        checkpoint_path,
        checkpoint_every_chunks,
        sdca_config,
    ) -> Tuple[GeneralizedLinearModel, SolverResult]:
        """SDCA arm of ``run_streamed`` (optim/sdca.py): typed refusals at
        this boundary, then the chunk-local dual solve."""
        import numpy as np

        from photon_tpu.optim import sdca

        opt = self.config.optimizer
        lam = (self.config.regularization_weight
               if regularization_weight is None else regularization_weight)
        if self.config.regularization.l1_weight(lam) != 0.0:
            raise ValueError(
                "SDCA has no dual coordinate step for the L1 term "
                "(the conjugate of |.| is an indicator, not a smooth box); "
                "use OWLQN for L1/elastic-net")
        if initial is not None and bool(np.any(np.asarray(initial) != 0)):
            raise ValueError(
                "SDCA cannot warm-start from nonzero coefficients: the "
                "dual decomposition w = v / l2 requires v = sum alpha_i "
                "x_i, and an arbitrary w has no dual preimage; start from "
                "zeros or use the streamed L-BFGS path for warm-started "
                "sweeps")
        cfg = sdca_config if sdca_config is not None else sdca.SdcaConfig(
            max_epochs=opt.max_iterations, gap_tolerance=opt.tolerance)
        result = sdca.minimize_sdca(
            self.objective, loader,
            l2_weight=self.config.regularization.l2_weight(lam),
            config=cfg, dim=dim, dtype=dtype,
            checkpoint_path=checkpoint_path,
            checkpoint_every_chunks=checkpoint_every_chunks)
        # minimize_sdca refuses non-identity norms, so coef is model space
        model = GeneralizedLinearModel(Coefficients(result.coef), self.task)
        return model, result

    def run_sdca_resident(
        self,
        batch: DataBatch,
        dim: Optional[int] = None,
        dtype=None,
        regularization_weight: Optional[float] = None,
        chunk_rows: int = 8192,
        sdca_config=None,
    ) -> Tuple[GeneralizedLinearModel, SolverResult]:
        """SDCA over a RESIDENT batch: re-streams the device arrays
        through the chunk pipeline (EllSource/DenseSource wrap host
        views) so the one solver serves both the disk-native and the
        in-core case. The fixed-effect coordinate passthrough lands here
        when the configured optimizer is ``OptimizerType.SDCA``."""
        import numpy as np

        from photon_tpu.data import streaming as dstream
        from photon_tpu.ops.features import (
            ModelShardedSparse,
            SparseFeatures,
        )

        feats = batch.features
        if isinstance(feats, ModelShardedSparse):
            raise ValueError(
                "SDCA keeps the full primal carry v per sample shard, "
                "which contradicts model-axis sharding of theta; use the "
                "streamed L-BFGS path for model-sharded coordinates")
        np_leaf = lambda a: None if a is None else np.asarray(a)
        if isinstance(feats, SparseFeatures):
            if dim is None:
                raise ValueError(
                    "run_sdca_resident needs dim for sparse features "
                    "(ELL indices do not bound the model width)")
            src = dstream.EllSource(
                np_leaf(feats.indices), np_leaf(feats.values),
                np_leaf(batch.labels), dim=int(dim),
                offsets=np_leaf(batch.offsets),
                weights=np_leaf(batch.weights))
        else:
            src = dstream.DenseSource(
                np_leaf(feats), np_leaf(batch.labels),
                offsets=np_leaf(batch.offsets),
                weights=np_leaf(batch.weights))
        if dtype is None:
            dtype = batch.labels.dtype
        loader = dstream.ChunkLoader(
            src, dstream.StreamConfig(chunk_rows=chunk_rows,
                                      dtype=np.dtype(dtype)))
        return self._run_sdca(
            loader, initial=None, dim=int(src.dim if dim is None else dim),
            dtype=dtype, regularization_weight=regularization_weight,
            checkpoint_path=None, checkpoint_every_chunks=0,
            sdca_config=sdca_config)

    # -- variances (reference: DistributedOptimizationProblem:82-100) -------

    @property
    def _variance_fns(self):
        """Default variance programs (non-mesh callers / tests)."""
        return self._variance_fns_for(VARIANCE_GRAM_BLOCK_ROWS)

    def _variance_fns_for(self, block_rows: Optional[int]):
        obj = self._var_objective  # original-space curvature (see __init__)
        precision = VARIANCE_GRAM_PRECISION

        def build():
            def of(variance_type):
                @jax.jit
                def variances(coef: Array, batch: DataBatch, l2: Array) -> Array:
                    return coefficient_variances(
                        obj, coef, batch, Hyper(l2_weight=l2), variance_type,
                        precision, block_rows)
                return variances

            return (of(VarianceComputationType.SIMPLE),
                    of(VarianceComputationType.FULL))

        key = ("glm_variance", self.task,
               norm_cache_key(self._var_objective.norm), precision, block_rows)
        return jitcache.get_or_build(key, build)

    def compute_variances(
        self,
        batch: DataBatch,
        coef: Array,
        variance_type: VarianceComputationType,
        regularization_weight: Optional[float] = None,
        mesh=None,
    ) -> Optional[Array]:
        """``mesh``: the batch is sample-sharded over it (``run``'s), so
        FULL's Gram stays ONE contraction whose partial sums the mesh
        reduces; on one device it is summed in row blocks
        (``VARIANCE_GRAM_BLOCK_ROWS``)."""
        if variance_type == VarianceComputationType.NONE:
            return None
        if not self.objective.loss.has_hessian:
            return None  # first-order-only losses (smoothed hinge)
        lam = (self.config.regularization_weight
               if regularization_weight is None else regularization_weight)
        l2 = jnp.asarray(self.config.regularization.l2_weight(lam), coef.dtype)
        simple, full = self._variance_fns_for(
            VARIANCE_GRAM_BLOCK_ROWS if mesh is None else None)
        if variance_type == VarianceComputationType.SIMPLE:
            return simple(coef, batch, l2)
        return full(coef, batch, l2)


# (At the end of the module: the fused kernel's serialised body carries its
# traceback's line numbers, so a line added above ``solve`` re-keys the
# compile cache of every kernel-bearing program; PERF.md §6, PR 33.)
#
# TRON's explicit-or-matrix-free gate, by what a solve can observe: dense
# features, the coefficient dimension, the backend. On a TPU v5e (PERF.md §5,
# my chip runs, PR 34; ms inside one program, float32 rows): one explicit
# ``X^T D X`` build at DEFAULT precision / one matrix-free product is 5.4 /
# 5.4 at 4,000,000 x 128, where ``pallas_glm.dense_route`` leaves the product
# to XLA's two passes (the build is bound by its two reads of X, as the
# product is, so explicit pays from the first CG step: whole fits 0.100
# against 0.133 s). From ``pallas_glm._DENSE_MIN_WIDTH`` = 256 features up
# the product is ONE read of X through the fused kernel and a build costs
# 1.5 products at 256 (10.8 / 7.3), 2.0 at 512 (10.9 / 5.4), 2.3 at 1,024
# (12.3 / 5.4) and 4.5 at 2,000 (25.9 / 5.8); the table's fits take 1.3-1.6
# CG steps a build and matrix-free wins them: 0.099 against 0.106 s at 256,
# 0.107 against 0.126 at 1,024, 0.126 against 0.207 at epsilon's 2,000.
# So the gate is the last width whose product still reads X twice (until
# PR 34 it was 1,024: a product read X twice at every width and a build
# stayed within a fifth of one up to there). Past it a build pays only on a
# problem that needs two CG steps a build or more, which a solve cannot
# know before it runs; ``explicit_hessian=True`` is there for one that does.
# On a host CPU the crossover sits between d = 256 (explicit 1.5x faster)
# and d = 512 (1.3x slower).
TRON_EXPLICIT_MAX_DIM_TPU = 255
TRON_EXPLICIT_MAX_DIM_CPU = 256


def tron_explicit_hessian(dense: bool, dim: int) -> bool:
    """Whether TRON builds ``X^T D X`` once an accepted step (True) or
    applies it matrix-free, a read of X a CG step through the fused kernel
    and two off it (False), where the configuration leaves
    ``explicit_hessian`` at None."""
    return dense and dim <= (TRON_EXPLICIT_MAX_DIM_CPU
                             if jax.default_backend() == "cpu"
                             else TRON_EXPLICIT_MAX_DIM_TPU)


# The Gram whose RESULT is published: ``FULL`` variances are
# ``diag((X^T D X + l2 I)^-1)``, so here the Hessian is no means to an optimum
# that an exact gradient corrects (NEWTON's and TRON's stay at DEFAULT, one
# bfloat16 pass of the MXU in one contraction: ``ops/features.weighted_gram``)
# but the number a ``BayesianLinearModelAvro`` carries. Its products are
# taken at ``VARIANCE_GRAM_PRECISION`` and its rows summed
# ``VARIANCE_GRAM_BLOCK_ROWS`` at a time
# (``features._upper_gram_in_row_blocks``).
# At 530,000 x 2,000 on a TPU v5e, against a float32 reference at the fitted
# means (PERF.md section 5, my chip runs, PR 40; ms a Gram): HIGHEST in blocks
# 7.4e-7 (149 ms), HIGH in blocks 8.7e-6 (72), DEFAULT 3.3e-5 (27), HIGHEST in
# one contraction 2.2e-5 (139), bfloat16 features 3.9e-5: only the first is
# float32's, and the cell's limit (5.4e-6) refuses the rest. So the speed
# since PR 41 comes from the work, not from either constant: summed in row
# blocks, the Gram has only the UPPER triangle's column blocks formed
# (``features.GRAM_COLUMN_BLOCK`` columns each), the same products over the
# same 8,192-row blocks, and the lower triangle mirrored ONCE after the last
# row block (``features.gram_route`` says which way a Gram goes, to the Gram
# and to ``kernels.variance_gram{path}`` alike).
VARIANCE_GRAM_PRECISION = jax.lax.Precision.HIGHEST
VARIANCE_GRAM_BLOCK_ROWS = 8192


def coefficient_variances(obj: GLMObjective, coef: Array, batch: DataBatch,
                          hyper: Hyper, variance_type: VarianceComputationType,
                          precision=None,
                          block_rows: Optional[int] = None) -> Array:
    """Coefficient variances at ``coef`` (reference:
    DistributedOptimizationProblem.computeVariances :82-100): SIMPLE =
    ``1 / diag(H)``, FULL = ``diag(H^-1)`` by a Cholesky inverse, ``H`` the
    regularised Hessian of ``obj`` on ``batch``. Traced inside a jitted
    program (the fixed effect's ``_variance_fns``; under ``vmap`` the
    per-entity ``RandomEffectCoordinate._variance_fn``), under the scopes
    ``optim/variance/{hessian,factor_solve,diagonal}``."""
    from photon_tpu.obs.metrics import registry
    from photon_tpu.ops import aggregators
    from photon_tpu.ops.features import gram_route

    precision = VARIANCE_GRAM_PRECISION if precision is None else precision
    if variance_type == VarianceComputationType.SIMPLE:
        with jax.named_scope("optim/variance/hessian"):
            d = obj.hessian_diagonal(coef, batch, hyper)
        with jax.named_scope("optim/variance/diagonal"):
            return 1.0 / jnp.maximum(d, jnp.finfo(d.dtype).tiny)
    # ticked at TRACE time: once a traced FULL program, with the precision
    # its Gram was traced at (a sparse Gram is scatter-adds, exact at any)
    # and the way it goes: ``dense_upper`` the upper triangle in row blocks,
    # ``dense`` one full product a contraction, ``sparse``
    registry.counter(
        "kernels.variance_gram", precision=precision.name,
        path=gram_route(batch.features, block_rows)).inc()
    dim = coef.shape[0]
    with jax.named_scope("optim/variance/hessian"):
        h = aggregators.hessian_matrix_from_weights(
            batch.features, obj.hessian_weights(coef, batch), obj.norm, dim,
            precision, block_rows)
        h = h + hyper.l2_weight * jnp.eye(dim, dtype=h.dtype)
    with jax.named_scope("optim/variance/factor_solve"):
        # diag(H^-1) via Cholesky (reference: util/Linalg Cholesky solves)
        chol = jax.scipy.linalg.cho_factor(h)
        hinv = jax.scipy.linalg.cho_solve(
            chol, jnp.eye(dim, dtype=h.dtype))
    with jax.named_scope("optim/variance/diagonal"):
        return jnp.diag(hinv)
