"""TrainEngine: the one sharded, donated train step every consumer runs.

Given ``(model, TrainConfig, ParallelConfig, mesh)`` the engine assembles
the full sharded TrainState story once:

  * abstract state (ShapeDtypeStructs — zero allocation, what ``lower``
    lowers against) and concrete sharded init (``init_state``),
  * per-leaf NamedShardings for params (via the ``models/params.py``
    logical-axis rules), optimizer state (via the Optimizer protocol's
    ``state_logical_axes`` — AdamW moments shard like their params,
    Adafactor's factored row/col second moments get the 1-D pspecs of the
    surviving axes), scaler state and the input batch,
  * a jitted train step with ``donate_argnums=(0,)`` whose in_shardings
    pin the state/batch layout, wrapped so every call (and trace) runs
    under the mesh + ShardCtx (activation constraints).

Consumers: ``launch/train.py`` and the benchmark train through it, tests
assert parity between meshes, and the Trainer resumes checkpoints onto
``engine.state_shardings``. No consumer constructs optimizer-state
shardings by hand.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ParallelConfig, TrainConfig
from repro.core.precision import QuantPolicy
from repro.models import params as PRM
from repro.models.params import (_divisible, abstract_params, default_rules,
                                 init_params, logical_to_pspec,
                                 specs_to_shardings)
from repro.train.train_step import (TrainState, make_train_setup,
                                    make_train_step)

def make_shard_ctx(mesh, parallel: ParallelConfig) -> PRM.ShardCtx:
    """Trace-time sharding context: activates activation constraints."""
    return PRM.ShardCtx(mesh, default_rules(parallel))


def batch_shardings(inputs, mesh: Mesh, rules):
    """NamedShardings for a train batch pytree by rank convention."""
    def one(v):
        if v.ndim == 4:                       # images (B, H, W, C)
            logical = ("batch", None, None, None)
        elif v.ndim == 3:                     # embeddings (B, S, D)
            logical = ("batch", "seq", None)
        elif v.ndim == 2:
            logical = ("batch", "seq")
        else:
            logical = ("batch",)
        ps = _divisible(v.shape, logical_to_pspec(logical, rules), mesh)
        return NamedSharding(mesh, ps)
    return jax.tree.map(one, inputs)


def _sds(x) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype)


def _axes_to_shardings(abs_tree, axes_tree, mesh, rules):
    """Zip a ShapeDtypeStruct tree with a matching logical-axes tree
    (tuple leaves, taken whole at the abstract tree's leaf positions)."""
    def one(a, ax):
        ps = _divisible(a.shape, logical_to_pspec(tuple(ax), rules), mesh)
        return NamedSharding(mesh, ps)
    return jax.tree.map(one, abs_tree, axes_tree)


@dataclasses.dataclass
class TrainEngine:
    bundle: Any
    train_cfg: TrainConfig
    parallel: ParallelConfig
    mesh: Mesh
    policy: QuantPolicy
    opt: Any
    scaler: Any
    rules: Dict
    specs: Dict                      # ParamSpec tree
    state_abs: TrainState            # ShapeDtypeStructs
    state_shardings: TrainState      # NamedShardings
    param_shardings: Any
    batch_spec: Any                  # ShapeDtypeStructs for one global batch
    batch_shardings: Any
    jit_step: Callable               # raw jitted step (for .lower)
    donate: bool

    def shard_ctx(self) -> PRM.ShardCtx:
        return make_shard_ctx(self.mesh, self.parallel)

    def step(self, state: TrainState, batch) -> tuple:
        """(state, batch) -> (state, metrics); state buffers are donated."""
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return self.jit_step(state, batch)

    def init_state(self, seed: int = 0) -> TrainState:
        """Concrete init, jitted with out_shardings so every leaf is born
        sharded — no host round-trip, no post-hoc device_put."""
        def init(key):
            params = init_params(self.specs, key)
            return TrainState(params, self.opt.init(params),
                              self.scaler.init(),
                              jnp.zeros((), jnp.int32),
                              jax.random.PRNGKey(seed))
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return jax.jit(init, out_shardings=self.state_shardings)(
                jax.random.PRNGKey(seed))

    def shard_batch(self, batch):
        """Place a host/global batch onto the mesh's batch shardings."""
        return jax.device_put(batch, self.batch_shardings)

    def make_supervisor(self, state, data_fn, *, checkpoint_dir: str,
                        config=None, fault_plan=None, **kw):
        """Self-healing trainer over this engine's step: detection →
        crc-verified checkpoint rewind → deterministic data skip
        (train/supervisor.py).  ``data_fn(j)`` must be a pure function of
        the data index; batches are sharded onto the engine's mesh here.
        ``fault_plan`` (train/faults.py) is the injection knob — None
        leaves the production path untouched."""
        from repro.train.supervisor import TrainSupervisor
        return TrainSupervisor(
            self.step, state, lambda j: self.shard_batch(data_fn(j)),
            checkpoint_dir=checkpoint_dir, config=config,
            state_shardings=self.state_shardings, fault_plan=fault_plan,
            **kw)

    def lower(self, batch_abs=None):
        """Lower the train step against abstract inputs."""
        batch_abs = self.batch_spec if batch_abs is None else batch_abs
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return self.jit_step.lower(self.state_abs, batch_abs)


def make_engine(model, train_cfg: TrainConfig, parallel: ParallelConfig,
                mesh: Mesh, batch_spec, *,
                policy: Optional[QuantPolicy] = None,
                donate: bool = True) -> TrainEngine:
    """Assemble the sharded train step for ``model`` on ``mesh``.

    ``model`` is an arch name, a config, or a prebuilt ModelBundle.
    ``batch_spec`` is a pytree of arrays or ShapeDtypeStructs giving one
    global batch's shapes (only shapes/dtypes are used).
    ``donate=False`` exists for the benchmark's no-donation baseline.
    """
    from repro.models import build
    if isinstance(model, str):
        from repro.configs import get_config
        model = get_config(model)
    bundle = model if hasattr(model, "param_specs") else build(model)

    assert tuple(mesh.axis_names) == tuple(parallel.mesh_axes), (
        f"mesh axes {mesh.axis_names} != ParallelConfig.mesh_axes "
        f"{parallel.mesh_axes}")

    policy = policy or QuantPolicy.from_train_config(train_cfg)
    opt, scaler = make_train_setup(train_cfg)
    rules = default_rules(parallel)

    specs = bundle.param_specs
    params_abs = abstract_params(specs)
    params_shard = specs_to_shardings(specs, mesh, rules)

    opt_abs = jax.eval_shape(opt.init, params_abs)
    if opt.state_logical_axes is not None:
        opt_shard = _axes_to_shardings(
            opt_abs, opt.state_logical_axes(specs), mesh, rules)
    else:                            # protocol not implemented: replicate
        opt_shard = jax.tree.map(lambda a: NamedSharding(mesh, P()), opt_abs)

    scaler_abs = jax.eval_shape(scaler.init)
    repl = NamedSharding(mesh, P())
    state_abs = TrainState(params_abs, opt_abs, scaler_abs,
                           jax.ShapeDtypeStruct((), jnp.int32),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
    state_shard = TrainState(params_shard, opt_shard,
                             jax.tree.map(lambda a: repl, scaler_abs),
                             repl, repl)

    batch_abs = jax.tree.map(_sds, batch_spec)
    batch_shard = batch_shardings(batch_abs, mesh, rules)

    step_fn = make_train_step(bundle, policy, parallel, train_cfg, opt,
                              scaler)
    jit_step = jax.jit(step_fn, in_shardings=(state_shard, batch_shard),
                       donate_argnums=(0,) if donate else ())

    return TrainEngine(bundle=bundle, train_cfg=train_cfg, parallel=parallel,
                       mesh=mesh, policy=policy, opt=opt, scaler=scaler,
                       rules=rules, specs=specs, state_abs=state_abs,
                       state_shardings=state_shard,
                       param_shardings=params_shard, batch_spec=batch_abs,
                       batch_shardings=batch_shard, jit_step=jit_step,
                       donate=donate)
