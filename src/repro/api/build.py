"""``build(experiment) -> Run`` — the one entrypoint every consumer shares.

``launch.train``, ``launch.dryrun``, ``benchmarks.run`` and checkpoint
resume all construct runs here, so a scenario is a data edit (an
:class:`~repro.api.spec.Experiment`), never a bespoke kwargs pile.  The
returned :class:`Run` exposes the uniform surface:

* ``init(key) -> state`` / ``step(state, batch) -> (state, metrics)`` — the
  exact factory-built pair (bit-identical to calling the
  ``make_*_train_step`` factory by hand with the same knobs);
* ``views(state)`` — the legacy pytree train state (identity on the unfused
  path, ``train_step.views`` on the flat substrate);
* ``shardings(state)`` — ``NamedSharding`` pytree for jit boundaries (None
  off-mesh);
* ``eval_fn(state) -> float`` — client-0 validation loss on a fixed batch;
* ``batch_fn(key)`` / ``place_batch(batch)`` — the synthetic federated
  stream and its mesh placement;
* ``spec`` — the (validated) Experiment itself, so a Run can always be
  reproduced, serialized, or embedded in a checkpoint.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

from repro.api.spec import Experiment
from repro.federation.participation import ParticipationSpec


class Run(NamedTuple):
    """A built Experiment (see the module docstring)."""
    spec: Experiment
    init: Any
    step: Any
    views: Any
    shardings: Any
    eval_fn: Any
    batch_fn: Any
    place_batch: Any
    model: Any
    model_cfg: Any
    fed: Any
    participation: Optional[ParticipationSpec]
    mesh: Any

    @property
    def steps(self) -> int:
        return self.spec.schedule.steps


def _resolve_participation(exp: Experiment) -> ParticipationSpec | None:
    """The ParticipationSpec the factories consume — ``None`` for the full
    sampler (the bit-exact no-participation fast path), with weighted
    samplers inheriting ``problem.client_sizes`` when the participation spec
    itself carries no weights."""
    p = exp.participation
    if p.sampler == "full":
        return None
    if (p.sampler == "weighted" and p.client_weights is None
            and exp.problem.client_sizes is not None):
        p = p._replace(client_weights=exp.problem.client_sizes)
    return p


def _resolve_mesh(exp: Experiment):
    """(mesh, mesh_arg): the jax Mesh of ``execution.mesh`` (None off-mesh)
    and what the factory's ``mesh=`` kwarg receives (a ShardCtx when
    scatter-comm lowering is requested)."""
    ex = exp.execution
    if ex.mesh is None:
        return None, None
    from repro.launch.mesh import make_debug_mesh, make_production_mesh
    if ex.mesh == "production":
        mesh = make_production_mesh()
    else:
        mesh = make_debug_mesh(*ex.mesh)
    if ex.scatter_comm:
        from repro.optim.flat import make_shard_ctx
        return mesh, make_shard_ctx(mesh, use_scatter=True)
    return mesh, mesh


def federated_config(exp: Experiment):
    """The :class:`~repro.config.FederatedConfig` an Experiment denotes
    (schedule + problem size + the algorithm's cfg-field hyperparams)."""
    from repro.api import registry
    from repro.config import FederatedConfig

    entry = registry.get(exp.algorithm.name)
    cfg_over, _ = entry.split_params(exp.algorithm.params_dict)
    sch = exp.schedule
    return FederatedConfig(
        algorithm=exp.algorithm.name, num_clients=exp.problem.num_clients,
        local_steps=sch.local_steps, lr_x=sch.lr_x, lr_y=sch.lr_y,
        lr_u=sch.lr_u, hierarchy_period=sch.hierarchy_period,
        hierarchy_groups=sch.hierarchy_groups, neumann_q=sch.neumann_q,
        neumann_tau=sch.neumann_tau, lower_l2=sch.lower_l2, seed=sch.seed,
        **cfg_over)


def build(experiment: Experiment) -> Run:
    """Compile a (validated) Experiment into a :class:`Run`."""
    import jax
    import jax.numpy as jnp

    from repro.api import registry
    from repro.configs import get_config
    from repro.data import make_fed_batch_fn
    from repro.models import build_model

    # canonical spec: sampler promotions applied ONCE here, so one JSON
    # means one run for every consumer (train CLI, dryrun, benchmarks,
    # resume) and Run.spec / embedded checkpoint specs are normal forms
    exp = experiment.validate().normalize()
    prob, ex = exp.problem, exp.execution

    model_cfg = get_config(prob.arch)
    if prob.reduced:
        model_cfg = model_cfg.reduced()
    if prob.param_dtype == "auto":
        dtype = jnp.float32 if prob.reduced else jnp.bfloat16
    else:
        dtype = jnp.dtype(prob.param_dtype)
    model = build_model(model_cfg, dtype=dtype)

    fed = federated_config(exp)
    entry = registry.get(exp.algorithm.name)
    _, factory_kw = entry.split_params(exp.algorithm.params_dict)
    pspec = _resolve_participation(exp)
    mesh, mesh_arg = _resolve_mesh(exp)

    init, step = entry.factory(
        model, fed, n_micro=ex.n_micro, remat=ex.remat,
        use_flash=ex.use_flash, use_lru_kernel=ex.use_lru_kernel,
        fuse_oracles=ex.fuse_oracles, fuse_storm=ex.fuse_storm,
        storm_block=ex.storm_block, participation=pspec,
        mesh=mesh_arg, overlap=ex.overlap,
        comm_every=exp.schedule.comm_every_dict or None,
        faults=exp.faults, robustness=exp.robustness,
        compression=exp.compression, telemetry=exp.telemetry,
        stragglers=exp.stragglers,
        **factory_kw)

    views = step.views if hasattr(step, "views") else (lambda s: s)
    shardings = (step.shardings if getattr(step, "shardings", None)
                 else (lambda s: None))

    batch_fn = make_fed_batch_fn(model_cfg, num_clients=prob.num_clients,
                                 per_client=prob.per_client,
                                 seq_len=prob.seq_len, seed=prob.data_seed)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        b_shard = NamedSharding(mesh, P("data"))

        def place_batch(b):
            return jax.device_put(b, jax.tree.map(lambda _: b_shard, b))
    else:
        place_batch = lambda b: b

    # the eval batch is fixed — generated once, client 0's validation split
    eval_batch = jax.tree.map(lambda v: v[0],
                              batch_fn(jax.random.PRNGKey(123)))

    @jax.jit
    def eval_loss(state, val):
        s = views(state)
        p = (s.params if hasattr(s, "params")
             else {"body": s.x, "head": s.y})
        p0 = jax.tree.map(lambda v: v[0], p)
        return model.loss(p0, val)[0]

    def eval_fn(state) -> float:
        return float(eval_loss(state, eval_batch["val"]))

    return Run(spec=exp, init=init, step=step, views=views,
               shardings=shardings, eval_fn=eval_fn, batch_fn=batch_fn,
               place_batch=place_batch, model=model, model_cfg=model_cfg,
               fed=fed, participation=pspec, mesh=mesh)
