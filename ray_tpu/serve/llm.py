"""Tensor-parallel sharded LLM behind the continuous-batching engine.

A model that one chip cannot hold is served over a MESH: weights AND the
paged KV pool are sharded over a ``tp`` axis, the engine's prefill-chunk
and decode-step programs are jitted once over the mesh with the pool
donated, and XLA inserts the attention/MLP output-projection psums that
ride ICI.  The reference never solves this inside Serve — its replicas
wrap user torch modules and model sharding happens outside (reference:
python/ray/serve/_private/replica.py:58); here the sharded model IS the
replica's model, so a deployment scales from one chip (tp=1) to a pod
slice by changing one argument.

Sharding layout (megatron-style, from LlamaModel.param_pspecs):
  wq/wk/wv/w_gate/w_up : [L, E, out]  — out (heads / ffn) split over tp
  wo/w_down            : [L, in, E]   — in split over tp (psum after)
  tok_emb / out_head   : vocab split over tp (psum gather / sharded logits)
  KV page pool         : [L, pages, page, KV, D] — KV heads split over tp
  experts (n_experts)  : w_gate/w_up [L, X, E, H], w_down [L, X, H, E] — whole
                         experts split over tp on X (psum after the
                         down-projection); router and QK-norm scales replicated
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import numpy as np

from ray_tpu.models.llama import LlamaConfig

__all__ = ["ShardedLLM", "engine_llm_deployment"]


def _resolve_cfg(model, max_seq_len):
    """LlamaConfig from a constructor name or an instance (worker-side)."""
    import dataclasses

    import jax.numpy as jnp

    if isinstance(model, LlamaConfig):
        return (
            model
            if max_seq_len is None
            else dataclasses.replace(model, max_seq_len=max_seq_len)
        )
    return getattr(LlamaConfig, model)(
        max_seq_len=max_seq_len or 256, param_dtype=jnp.bfloat16
    )


def _parse_prompt_spec(spec, vocab_size: int, default_new: int):
    """Normalize the three accepted request shapes into
    (prompt_ids, max_new_tokens, eos_token):

    - int seed       -> one-token prompt
    - [ids...]       -> explicit prompt
    - {"prompt": int|[ids...], "max_new_tokens": n, "eos_token": t}
    """
    max_new, eos = default_new, None
    if isinstance(spec, dict):
        max_new = int(spec.get("max_new_tokens") or default_new)
        eos = spec.get("eos_token")
        eos = None if eos is None else int(eos)
        spec = spec.get("prompt", 0)
    if isinstance(spec, (list, tuple)):
        ids = [int(t) % vocab_size for t in spec]
    else:
        ids = [int(spec) % vocab_size]
    return ids, max_new, eos


def _filter_spec(spec, axis_names):
    """Drop mesh axes the serving mesh doesn't have (e.g. the training
    pspecs name fsdp; a pure-tp serving mesh replicates those dims)."""
    from jax.sharding import PartitionSpec as P

    return P(*(a if a in axis_names else None for a in spec))


class ShardedLLM:
    """A ``LlamaConfig`` model -- dense (Llama, Mistral), sparse-expert
    with QK-norm (OLMoE), or a subclass that builds its own model
    (``cfg.build_model()``: Qwen3-Next) -- sharded over a 1-D tp mesh; ``engine_programs``
    gives the serving engine its jitted programs over that mesh.

    init:
      "random" — normal(0, 0.02) weights (serving without a ckpt)
      "cheap"  — deterministic iota-pattern fill (dryrun at 7B shape: no
                 7-billion-sample RNG on a 1-core host; still exercises
                 every collective with non-trivial values)
      dict     — a params pytree (or host arrays) to shard onto the mesh
      "abstract" — no weights: ``params`` is the tree of
                 ``jax.ShapeDtypeStruct``s with the replica's shardings, to
                 compile ``engine_programs`` ahead of time
                 (``.lower(llm.params, ...)``), also over the devices of a
                 TPU topology this host has no chip of
                 (tests/test_weight_copies.py)
    """

    def __init__(
        self,
        cfg: LlamaConfig,
        devices: Optional[Sequence[Any]] = None,
        tp: Optional[int] = None,
        init: Any = "random",
        seed: int = 0,
    ):
        import jax
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        devices = list(devices if devices is not None else jax.devices())
        tp = int(tp or len(devices))
        if tp > len(devices):
            raise ValueError(f"tp={tp} but only {len(devices)} devices")
        for dim, name in (
            (cfg.n_kv_heads, "n_kv_heads"),
            # what tp splits in the FFN: whole experts, or a dense FFN's width
            (cfg.n_experts, "n_experts") if cfg.n_experts else (cfg.hidden_dim, "hidden_dim"),
            (cfg.padded_vocab, "padded_vocab"),
            (cfg.dim, "dim"),
        ):
            if dim % tp:
                raise ValueError(f"{name}={dim} not divisible by tp={tp}")
        self.cfg = cfg
        self.tp = tp
        self.model = cfg.build_model()
        self.mesh = Mesh(np.array(devices[:tp]), ("tp",))

        pspecs = self.model.param_pspecs()
        self.param_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, _filter_spec(s, ("tp",))),
            pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )

        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(seed))
        if isinstance(init, dict):
            self.params = self.place(init)
        elif init == "abstract":
            self.params = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), shapes, self.param_shardings
            )
        elif init == "cheap":
            # deterministic per-shard numpy fill via make_array_from_callback
            # — no XLA init program, no 2x cast transients; each device
            # writes only ITS shard.  Values vary over the last two dims
            # (broadcast over leading), which is non-degenerate enough to
            # exercise every collective with real data at 7B shape on a
            # 1-core dryrun host in tens of seconds.
            import zlib

            def fill(path, s, sharding):
                if "norm" in path:
                    return jax.make_array_from_callback(
                        s.shape,
                        sharding,
                        lambda idx: np.ones(
                            tuple(
                                len(range(*sl.indices(d)))
                                for sl, d in zip(idx, s.shape)
                            ),
                            s.dtype,
                        ),
                    )
                salt = zlib.crc32(path.encode())

                def cb(idx):
                    sl = [range(*x.indices(d)) for x, d in zip(idx, s.shape)]
                    shape = tuple(len(r) for r in sl)
                    j = np.arange(sl[-1].start, sl[-1].stop, dtype=np.int64)
                    col = ((j * 2654435761 + salt) % 1009) / 1009.0 - 0.5
                    if len(shape) >= 2:
                        i = np.arange(sl[-2].start, sl[-2].stop, dtype=np.int64)
                        row = ((i * 40503 + salt) % 997) / 997.0 - 0.5
                        mat = (col[None, :] + row[:, None]) * 0.02
                    else:
                        mat = col * 0.02
                    out = np.broadcast_to(mat, shape).astype(s.dtype)
                    return np.ascontiguousarray(out)

                return jax.make_array_from_callback(s.shape, sharding, cb)

            params = {}
            for k, v in shapes.items():
                if isinstance(v, dict):
                    params[k] = {
                        k2: fill(k2, s, self.param_shardings[k][k2])
                        for k2, s in v.items()
                    }
                else:
                    params[k] = fill(k, v, self.param_shardings[k])
            self.params = params
        elif init == "random":
            self.params = jax.jit(
                self.model.init, out_shardings=self.param_shardings
            )(jax.random.PRNGKey(seed))
        else:
            raise ValueError(f"unknown init {init!r}")

    def place(self, params):
        """``params`` (a tree like ``self.params``, of device or host
        arrays) on the mesh with the shardings ``self.params`` has -- what
        keeps a weight swap on the two programs already compiled."""
        import jax

        return jax.tree.map(jax.device_put, params, self.param_shardings)

    def engine_programs(self, *, num_pages: int, page_size: int, num_slots: int = 0) -> Dict[str, Any]:
        """The continuous-batching engine's three jitted programs over
        THIS mesh: page-pool init, prefill chunk, decode step
        (models/llama.py), and ``place``, which puts a host value where
        the programs put their token results.  The pool is sharded over its KV heads (tp) and
        DONATED into every call, so the engine's resident loop re-uses one
        in-place buffer per program — and because the paged programs are
        shaped by pool geometry only, the whole mixed-length fleet shares
        exactly one compiled decode shape (the engine asserts this via
        ``compile_stats``).

        Weights are stored row-major and both programs read every stack
        where it lies: no weight-sized copy runs in either
        (``LlamaModel._qkv`` says what it took; tests/test_weight_copies.py
        compiles them for the v5e without a chip and checks)."""
        import functools

        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        repl = NamedSharding(self.mesh, P())
        # explicit out_shardings keep the pool's NamedSharding STABLE
        # across calls: without them the first program's output drops to
        # an inferred sharding, which flips the next call's jit cache key
        # — one silent recompile per program, exactly what the engine's
        # no-recompilation contract forbids
        # (the pool's members are the model's: pages, an expert model's
        # routing counter, per-slot state -- ``model.pool_pspecs``)
        pool_sharding = tuple(NamedSharding(self.mesh, spec) for spec in self.model.pool_pspecs())
        step_out = (repl, pool_sharding)

        def decode_step_paged(params, pages, tables, tokens, positions, active, join_slot=None, join_token=None):
            # The engine keeps its token frontier on the device: ``tokens`` is
            # the step before's result, unread, and the one row that joins
            # the fleet this turn takes the token its last chunk sampled
            # (``join_token``, unread too) at ``join_slot``; -1 joins nobody.
            # The substitution rides inside this program because a program of
            # its own would be a third module on the device between the two
            # the benchmark's readers pair with their dispatches in order.
            # Called without the two, as the references call it, this is
            # ``model.decode_step_paged`` and nothing else.
            if join_slot is not None:
                import jax.numpy as jnp

                tokens = jnp.where(jnp.arange(tokens.shape[0]) == join_slot, join_token, tokens)
            return self.model.decode_step_paged(params, pages, tables, tokens, positions, active, page_size=page_size)

        def program(fn, *args, **kwargs):
            # a bare partial has no __name__: the compiler would call the
            # module jit__unknown.  Named after the model's method, the
            # device trace's XLA Modules line and the host's PjitFunction
            # dispatch event carry the same stable name
            bound = functools.partial(fn, *args, **kwargs)
            bound.__name__ = fn.__name__
            return bound

        return {
            "init": jax.jit(
                program(self.model.init_pages, num_pages, page_size, num_slots),
                out_shardings=pool_sharding,
            ),
            "prefill": jax.jit(
                program(self.model.prefill_chunk_paged, page_size=page_size),
                donate_argnums=(1,),
                out_shardings=step_out,
            ),
            "decode": jax.jit(decode_step_paged, donate_argnums=(1,), out_shardings=step_out),
            # a host value placed as the programs' token results are: what the
            # engine's frontier starts from, so that the decode program sees
            # ``tokens`` of one kind from its first call on
            "place": lambda x: jax.device_put(x, repl),
        }

    def param_count(self) -> int:
        import jax

        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params))

    def shard_stats(self) -> Dict[str, Any]:
        """Total param bytes and per-device resident bytes — the evidence
        that the model actually lives 1/tp per chip."""
        import jax

        total = 0
        per_device: Dict[str, int] = {}
        for leaf in jax.tree_util.tree_leaves(self.params):
            total += leaf.nbytes
            for sh in leaf.addressable_shards:
                key = str(sh.device)
                per_device[key] = per_device.get(key, 0) + sh.data.nbytes
        return {"total_bytes": total, "per_device_bytes": per_device}


def engine_llm_deployment(
    model="llama_3b",
    *,
    max_seq_len: Optional[int] = None,
    new_tokens: int = 32,
    num_slots: int = 8,
    page_size: int = 16,
    num_pages: int = 0,
    prefill_chunk: int = 32,
    max_queue: int = 256,
    num_tpus: int = 1,
    tp: Optional[int] = None,
    name: str = "llm",
    autoscaling_config: Optional[dict] = None,
):
    """Build the Serve deployment of an LLM: the replica shards the model
    over its chips (:class:`ShardedLLM`) and hosts a resident
    :class:`~ray_tpu.serve.engine.InferenceEngine` (iteration-level
    scheduling over a paged KV cache).  Requests of any prompt length
    admit/retire per token step, tokens stream incrementally over
    dag-channel token streams (``handle.stream_tokens`` / SSE at the
    proxy), and a full admission queue rejects FAST with
    ``EngineOverloadedError`` (the proxy's 503).

    ``model`` is a LlamaConfig constructor name or a LlamaConfig INSTANCE
    (resolved worker-side either way).  The replica claims ``num_tpus``
    chips and shards over every device jax exposes inside the actor (tp
    defaults to all of them).  A request is one of
    ``_parse_prompt_spec``'s three shapes."""
    from ray_tpu import serve

    @serve.deployment(
        name=name,
        ray_actor_options={"num_tpus": num_tpus},
        max_concurrent_queries=max(256, max_queue),
        autoscaling_config=autoscaling_config
        or {
            "min_replicas": 1,
            "max_replicas": 1,
            "target_num_ongoing_requests_per_replica": 64,
        },
    )
    class LLMEngineDeployment:
        def __init__(self):
            import jax

            from ray_tpu.serve.engine import EngineConfig, InferenceEngine

            cfg = _resolve_cfg(model, max_seq_len)
            self.llm = ShardedLLM(cfg, tp=tp)
            self.engine = InferenceEngine(
                self.llm,
                EngineConfig(
                    num_slots=num_slots,
                    page_size=page_size,
                    max_seq_len=cfg.max_seq_len,
                    num_pages=num_pages,
                    prefill_chunk=prefill_chunk,
                    max_queue=max_queue,
                    max_new_tokens=new_tokens,
                ),
                deployment=name,
            )
            self.platform = jax.devices()[0].platform

        def _submit(self, prompt, max_new_tokens=None, eos_token=None, sink=None):
            from ray_tpu.serve import tracing as serve_tracing

            ids, spec_new, spec_eos = _parse_prompt_spec(
                prompt, self.llm.cfg.vocab_size, new_tokens
            )
            return self.engine.submit(
                ids,
                max_new_tokens if max_new_tokens is not None else spec_new,
                eos_token=eos_token if eos_token is not None else spec_eos,
                trace=serve_tracing.current_request(),
                sink=sink,
            )

        async def __call__(self, prompt):
            """Buffered (non-streaming) callers: submit and await the full
            sequence without blocking the replica's event loop — the
            engine thread resolves the future at retirement."""
            import asyncio

            from ray_tpu.exceptions import EngineStreamError

            req = self._submit(prompt)
            loop = asyncio.get_running_loop()
            fut = loop.create_future()

            def _done(sink):
                def _fin():
                    if fut.done():
                        return
                    if sink.error is not None:
                        fut.set_exception(EngineStreamError(sink.error))
                    else:
                        fut.set_result(list(sink.tokens))

                loop.call_soon_threadsafe(_fin)

            req.sink.add_done_callback(_done)
            return await fut

        # ---- streaming: dag-channel attach with an actor-call fallback

        def engine_stream_start(self, prompt, max_new_tokens=None, eos_token=None):
            import os

            from ray_tpu.serve.engine import transport

            st = transport.hub().create(
                outbox_limit=self.engine.cfg.stream_outbox_limit
            )
            try:
                req = self._submit(
                    prompt, max_new_tokens, eos_token=eos_token, sink=st
                )
            except BaseException:
                # rejected submit (overload/capacity): reap the stream
                # NOW — gc_finished only sweeps closed streams, and this
                # one would otherwise sit open in the hub forever under
                # exactly the sustained-overload condition
                transport.hub().remove(st.sid)
                raise
            st.cancel_cb = lambda: self.engine.cancel(req)
            return {
                "sid": st.sid,
                "node_id": os.environ.get("RAY_TPU_NODE_ID", ""),
            }

        async def engine_stream_next(self, sid, max_frames=16, timeout=30.0):
            """Pull-path fallback (no direct-call transport): drain the
            stream's outbox through the normal actor-call path.  Runs the
            blocking wait on an executor so concurrent requests keep
            flowing through the replica's loop."""
            import asyncio

            from ray_tpu.serve.engine import transport

            st = transport.hub().get(int(sid))
            if st is None:
                return [], True
            frames, done = await asyncio.get_running_loop().run_in_executor(
                None, st.pull, int(max_frames), float(timeout)
            )
            if done:
                transport.hub().remove(int(sid))
            return frames, done

        def engine_stream_cancel(self, sid):
            from ray_tpu.serve.engine import transport

            st = transport.hub().get(int(sid))
            if st is not None and st.cancel_cb is not None:
                st.cancel_cb()
            transport.hub().remove(int(sid))
            return True

        # ---- observe / manage

        def engine_stats(self):
            return self.engine.stats()

        def engine_load(self):
            """Cheap pressure snapshot for least-pressure routing
            (serve/FLEET.md): queue depth, slot occupancy, and KV-page
            fraction.  The Replica wrapper merges this into its load()
            report, which the controller piggybacks onto routing
            publishes — called at the load-poll period, so it must stay
            allocation-light."""
            st = self.engine.stats()
            pages_total = float(st.get("pages_total", 0.0) or 0.0)
            return {
                "queue_depth": float(st.get("queue_depth", 0.0)),
                "slots_active": float(st.get("slots_active", 0.0)),
                "slots_total": float(st.get("slots_total", 0.0)),
                "kv_page_frac": (
                    float(st.get("pages_used", 0.0)) / pages_total
                    if pages_total > 0
                    else 0.0
                ),
            }

        def engine_idle(self):
            """Drain-completion predicate (serve/FLEET.md): True only
            when the scheduler holds no queued or running requests AND
            every hub stream's consumer finished draining its outbox —
            a replica torn down earlier would drop frames a slow client
            had not pulled yet."""
            from ray_tpu.serve.engine import transport

            st = self.engine.stats()
            busy = st.get("queue_depth", 0.0) or st.get("slots_active", 0.0)
            return not busy and transport.hub().busy_count() == 0

        def defrag(self):
            return self.engine.defrag()

        def reconfigure(self, user_config):
            """Live knobs only (queue bound for load shedding); geometry
            is baked into compiled programs."""
            if user_config and "max_queue" in user_config:
                self.engine.reconfigure(max_queue=int(user_config["max_queue"]))

        def info(self):
            import jax

            local = jax.local_devices()
            return {
                "platform": self.platform,
                "device_kind": local[0].device_kind,
                "device_count": len(local),
                # weights + page pool; a program's temporaries are not in it
                "peak_bytes_in_use": max(
                    int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                    for d in local
                ),
                "params_b": round(self.llm.cfg.num_params() / 1e9, 2),
                "active_params_b": round(self.llm.cfg.active_params_per_token() / 1e9, 2),
                "tp": self.llm.tp,
                "engine": self.engine.stats(),
                "shards": self.llm.shard_stats(),
            }

    return LLMEngineDeployment
