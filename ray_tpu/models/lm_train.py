"""Sharded LM training step: the compute core under Train's JaxTrainer.

Builds a pjit-compiled (init, step) pair for a GPT2Model over an arbitrary
Mesh.  Replaces the reference's torch DDP/FSDP wrap + NCCL allreduce
(reference: python/ray/train/torch/train_loop_utils.py:56 prepare_model,
config.py:69 _setup_torch_process_group): here the mesh sharding IS the
strategy — dp replicates params and psums grads, fsdp shards params and
optimizer state (ZeRO-style), tp shards within layers — the collectives
inserted by XLA over ICI, but for fsdp's gather of a layer, which the model
states inside its layer scan (gpt2.py `backbone`): one layer's matrices in
the compute dtype a layer, their gradient reduced back to the shard in the
backward loop; params, moments and the update stay float32 and sharded.

Optimizer-state sharding (ZeRO-1, BASELINE config #4) falls out of the
same spec tree: mu/nu inherit each param's PartitionSpec, so any param
sharded over `fsdp` has its Adam moments sharded identically, and the
moments of a param that replicates (embeddings, the per-layer vectors) are
sharded all the same (`_tree_specs_for_opt_state`).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.gpt2 import GPT2Config, GPT2Model
from ray_tpu.parallel.mesh import data_pspec, prune_pspec


def _tree_specs_for_opt_state(opt, params, param_specs, mesh=None):
    """PartitionSpec tree for the optimizer state: moment tensors inherit
    their param's spec (path-suffix match), scalars replicate.

    ZeRO-1 completion: when the mesh carries an fsdp axis, moments whose
    param is NOT fsdp-sharded (embeddings, layernorms, biases) still get a
    shard — Adam's elementwise math lets the moments live sharded while
    the param replicates; XLA all-gathers the sharded update before
    apply.  This is exactly the reference FSDP/ZeRO-1 optimizer-state
    memory split (torch train_loop_utils.py:29-31) without touching the
    forward's tuned layouts."""
    from jax.tree_util import tree_flatten_with_path, tree_map_with_path

    flat, _ = tree_flatten_with_path(param_specs)
    by_path = {tuple(str(k) for k in path): spec for path, spec in flat}
    shapes = jax.eval_shape(opt.init, params)
    fsdp_n = 0
    if mesh is not None and "fsdp" in mesh.axis_names:
        fsdp_n = mesh.shape["fsdp"]

    def leaf_spec(path, leaf):
        if getattr(leaf, "ndim", 0) == 0:
            return P()
        pstr = tuple(str(k) for k in path)
        spec = P()
        for start in range(len(pstr)):
            if pstr[start:] in by_path:
                spec = by_path[pstr[start:]]
                break
        if fsdp_n > 1 and all(a is None for a in spec):
            # fully-replicated moment: shard the first fsdp-divisible dim
            for d, size in enumerate(leaf.shape):
                if size % fsdp_n == 0:
                    return P(*([None] * d), "fsdp")
        return spec

    return tree_map_with_path(leaf_spec, shapes)


class TrainStepBundle(NamedTuple):
    init: Any  # (rng) -> (params, opt_state)
    step: Any  # (params, opt_state, tokens, targets) -> (params, opt_state, metrics)
    mesh: Any
    param_shardings: Any
    opt_shardings: Any
    batch_sharding: Any


def make_train_step(
    model: GPT2Model,
    mesh: Mesh,
    *,
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    grad_clip: float = 1.0,
    optimizer=None,
) -> TrainStepBundle:
    import optax

    cfg = model.config
    if optimizer is None:
        optimizer = optax.chain(
            optax.clip_by_global_norm(grad_clip),
            optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=weight_decay),
        )

    param_specs = model.param_pspecs(mesh)
    # drop axes the mesh doesn't carry (e.g. running a tp-annotated model on
    # a pure-dp mesh)
    param_specs = jax.tree.map(
        lambda spec: prune_pspec(spec, mesh), param_specs, is_leaf=lambda x: isinstance(x, P)
    )

    def shard(spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P)
        )

    param_shardings = shard(param_specs)
    batch_spec = data_pspec(mesh)
    batch_sharding = NamedSharding(mesh, batch_spec)

    dummy = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_specs = _tree_specs_for_opt_state(optimizer, dummy, param_specs, mesh)
    opt_shardings = shard(opt_specs)

    @functools.partial(jax.jit, out_shardings=(param_shardings, opt_shardings))
    def init(rng):
        params = model.init(rng)
        return params, optimizer.init(params)

    def loss_fn(params, tokens, targets):
        return model.loss(params, tokens, targets, mesh)

    use_1f1b = (
        dict(mesh.shape).get("pp", 1) > 1
        and getattr(cfg, "pp_schedule", "gpipe") == "1f1b"
    )

    @functools.partial(
        jax.jit,
        in_shardings=(param_shardings, opt_shardings, batch_sharding, batch_sharding),
        out_shardings=(param_shardings, opt_shardings, None),
        donate_argnums=(0, 1),
    )
    def step(params, opt_state, tokens, targets):
        if use_1f1b:
            # explicit per-microbatch backward (activation memory bounded
            # by pipe depth); grads arrive from inside the schedule
            loss, grads = model.loss_and_grads_1f1b(params, tokens, targets, mesh)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return TrainStepBundle(init, step, mesh, param_shardings, opt_shardings, batch_sharding)


def synthetic_batch(rng: jax.Array, batch: int, seq: int, vocab: int):
    """Deterministic synthetic LM batch (benchmarks; reference analog:
    release/air_tests synthetic datasets)."""
    tokens = jax.random.randint(rng, (batch, seq + 1), 0, vocab, dtype=jnp.int32)
    return tokens[:, :-1], tokens[:, 1:]


# ---------------------------------------------------------------- step spec


def _lm_build(config, rank, world):
    """Worker-side build for the LM TrainStepSpec: model + jitted grad fn
    + optimizer, params device-resident from here on.  Same init seed on
    every rank (the DP contract test_train.py's eager loops use)."""
    import optax

    cfg = getattr(GPT2Config, config["model"])(compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(int(config["init_seed"])))
    opt = optax.adam(float(config["lr"]))
    opt_state = opt.init(params)
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t, g: model.loss(p, t, g)))
    return {
        "cfg": cfg,
        "params": params,
        "opt": opt,
        "opt_state": opt_state,
        "grad_fn": grad_fn,
        "rank": rank,
        "world": world,
        "batch": int(config["batch"]),
        "seq": int(config["seq"]),
        "sync_grads": bool(config["sync_grads"]),
        "data_seed": int(config["data_seed"]),
        "group": str(config.get("collective_group", "_train_dp")),
    }


def _lm_data(state, idx):
    """Deterministic in (rank, step_idx): checkpoint-resume replays the
    exact stream, which is what makes resumed weights bit-identical."""
    key = jax.random.PRNGKey(state["data_seed"] + idx * 1000 + state["rank"])
    return synthetic_batch(
        key, state["batch"], state["seq"], state["cfg"].vocab_size
    )


def _lm_step(state, batch):
    import optax

    tokens, targets = batch
    loss, grads = state["grad_fn"](state["params"], tokens, targets)
    if state["world"] > 1 and state["sync_grads"]:
        from ray_tpu.train.jax.train_loop_utils import all_reduce_pytree

        grads = all_reduce_pytree(grads, state["world"], group_name=state["group"])
    updates, state["opt_state"] = state["opt"].update(grads, state["opt_state"])
    state["params"] = optax.apply_updates(state["params"], updates)
    return {"loss": loss}


def _lm_fold(state, metrics):
    return {"loss": float(metrics["loss"])}


def _lm_snapshot(state):
    import numpy as np

    return jax.tree.map(
        lambda x: np.asarray(x),
        {"params": state["params"], "opt_state": state["opt_state"]},
    )


def _lm_restore(state, snap):
    state["params"] = jax.tree.map(jnp.asarray, snap["params"])
    state["opt_state"] = jax.tree.map(jnp.asarray, snap["opt_state"])


def make_lm_step_spec(
    model: str = "tiny",
    *,
    batch: int = 4,
    seq: Optional[int] = None,
    steps: int = 10,
    learning_rate: float = 1e-2,
    checkpoint_every: int = 0,
    sync_grads: bool = True,
    init_seed: int = 0,
    data_seed: int = 1,
    collective_group: str = "_train_dp",
    name: str = "lm_train_dag",
):
    """A GPT-2 training run as a ``TrainStepSpec`` (train/jax/step_dag.py):
    the SAME stage functions drive both the eager per-step path and the
    gang-scheduled resident DAG, so eager-vs-dag weight equality is a
    property of the system, not the workload.  Used by the multichip
    dryrun's gang phase and tests/test_train_dag.py."""
    from ray_tpu.train.jax.step_dag import TrainStepSpec

    cfg = getattr(GPT2Config, model)()
    seq = seq or cfg.block_size
    return TrainStepSpec(
        build=_lm_build,
        data=_lm_data,
        step=_lm_step,
        fold=_lm_fold,
        snapshot=_lm_snapshot,
        restore=_lm_restore,
        steps=steps,
        checkpoint_every=checkpoint_every,
        config={
            "model": model,
            "batch": batch,
            "seq": seq,
            "lr": learning_rate,
            "sync_grads": sync_grads,
            "init_seed": init_seed,
            "data_seed": data_seed,
            # must match JaxConfig.group_name (default TRAIN_GROUP): the
            # step stage reduces on this group, the backend creates it
            "collective_group": collective_group,
        },
        name=name,
        flops_per_step=cfg.flops_per_token() * batch * seq,
    )
