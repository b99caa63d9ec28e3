"""GPT-2 family, TPU-first.

The flagship model for the Train stack (BASELINE configs #2 and #4: 124M
data-parallel, 1.5B with ZeRO-1).  Design choices are MXU/HBM-driven, not a
port of any torch modeling code:

- params are a plain pytree with a *stacked* [n_layer, ...] leading dim and
  the forward is one `lax.scan` over layers → one compiled layer body,
  `jax.checkpoint` per layer for rematerialization (HBM ⇄ FLOPs trade).
- compute in bfloat16 (MXU native), master params float32, loss/softmax in
  float32; vocab padded to a multiple of 128 so the logits matmul tiles
  cleanly onto the 128×128 systolic array.
- sharding is declared, not wired: `param_pspecs()` returns a PartitionSpec
  pytree over the standard mesh axes (tp shards attention heads / mlp
  hidden / vocab; fsdp shards each layer's matrices on the dim tp leaves
  whole, never the stacked dim the scan walks; dp replicates), so the same
  model runs single-chip or on any Mesh via pjit with no code change.  The
  one collective the model states itself is fsdp's gather of a layer, inside
  the checkpointed layer (`backbone`).
- sequence parallelism: pass `mesh_axis_sp` to route attention through
  ring_attention (sequence sharded over the `sp` axis).

Reference surface parity: the reference ships no LM of its own — its Train
layer wraps user torch modules (reference: python/ray/train/torch/
train_loop_utils.py prepare_model).  This model is the `train_loop` payload
for our equivalents of the AIR GPT-2 release benchmarks
(reference: release/air_tests/air_benchmarks/).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import prune_pspec


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    block_size: int = 1024
    dropout: float = 0.0  # benchmarks run dropout-free (jit-friendly default)
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "full" recomputes everything; "dots" saves matmul outputs and only
    # recomputes elementwise ops; "lite" saves everything EXCEPT the
    # layernorm/gelu outputs (the cheapest recomputes with the biggest
    # buffers) — the least-recompute policy that still fits a v5e chip at
    # batch 16 with the splash attention kernel
    remat_policy: str = "dots"
    # "auto": pallas splash kernel on TPU, xla einsum elsewhere
    attention_impl: str = "auto"
    # what the QK^T matmul writes: f32 (safe) or bf16 (half the [S,S] HBM
    # traffic; softmax still accumulates f32)
    attn_scores_dtype: Any = jnp.float32
    use_ring_attention: bool = False
    # "fused": chunked linear-head CE that never materializes [B,S,V] logits
    # (ops/cross_entropy.py); "naive": full-logits path; "auto" picks fused
    # unless the sequence axis is sharded (sp ring attention), whose layout
    # the chunked scan would break
    loss_impl: str = "auto"
    # sequence-chunk length per fused-CE scan step; the transient logits
    # block is [B, loss_chunk, padded_vocab] f32.  0 = auto: ~4k tokens
    # per block (bigger blocks amortize scan overhead, measured +0.4 MFU
    # at b16; capped so large batches don't blow the transient)
    loss_chunk: int = 0
    # GPipe microbatches per data shard when the mesh carries a pp axis
    # (bubble fraction (pp-1)/(M+pp-1))
    pp_microbatches: int = 4
    # "gpipe": all-forward-then-autodiff-backward (activations for every
    # in-flight microbatch live across the schedule); "1f1b": explicit
    # per-microbatch backward with a min(M, 2pp-1)-deep activation ring —
    # same gradients, O(pp) activation memory, so M can grow at a fixed
    # budget and shrink the bubble (parallel/pipeline.py 1F1B notes)
    pp_schedule: str = "gpipe"
    # >0 turns every MLP into a top-1 switch MoE with this many experts
    # (parallel/moe.py); experts shard over the ep mesh axis
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25

    @property
    def padded_vocab(self) -> int:
        # 128-lane tiling for the MXU; 50257 → 50304
        return _round_up(self.vocab_size, 128)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @classmethod
    def gpt2_124m(cls, **kw) -> "GPT2Config":
        return cls(n_layer=12, n_head=12, n_embd=768, **kw)

    @classmethod
    def gpt2_350m(cls, **kw) -> "GPT2Config":
        return cls(n_layer=24, n_head=16, n_embd=1024, **kw)

    @classmethod
    def gpt2_774m(cls, **kw) -> "GPT2Config":
        return cls(n_layer=36, n_head=20, n_embd=1280, **kw)

    @classmethod
    def gpt2_1p5b(cls, **kw) -> "GPT2Config":
        return cls(n_layer=48, n_head=25, n_embd=1600, **kw)

    @classmethod
    def tiny(cls, **kw) -> "GPT2Config":
        """CPU-testable toy (virtual-mesh tests, dryruns)."""
        kw.setdefault("vocab_size", 512)
        kw.setdefault("block_size", 64)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 2)
        kw.setdefault("n_embd", 64)
        return cls(**kw)

    def num_params(self) -> int:
        V, L, E = self.padded_vocab, self.n_layer, self.n_embd
        per_layer = 12 * E * E + 13 * E  # qkv+proj+mlp(4x) + biases + 2 ln
        return V * E + self.block_size * E + L * per_layer + 2 * E

    def flops_per_token(self) -> float:
        """Training FLOPs/token = 6N + 12·L·E·S (PaLM appendix / nanoGPT
        convention): N is total params — wte is tied, used as both input
        embedding and the logits head matmul — plus the attention
        score/value matmuls.  This is the MFU numerator per token."""
        N = self.num_params()
        attn = 12 * self.n_layer * self.n_embd * self.block_size
        return 6.0 * N + attn


class GPT2Model:
    """Functional model: params are an explicit pytree; every method is
    jit/pjit-friendly (no hidden state)."""

    def __init__(self, config: GPT2Config):
        self.config = config

    # ------------------------------------------------------------ params

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        E, L, V, S = cfg.n_embd, cfg.n_layer, cfg.padded_vocab, cfg.block_size
        H = cfg.n_head
        k = iter(jax.random.split(rng, 16))
        std = 0.02
        proj_std = std / math.sqrt(2 * L)  # GPT-2 residual-stream scaling
        pd = cfg.param_dtype

        def norm(key, shape, s):
            return (jax.random.normal(key, shape) * s).astype(pd)

        params = {
            "wte": norm(next(k), (V, E), std),
            "wpe": norm(next(k), (S, E), std),
            "ln_f": {"scale": jnp.ones((E,), pd), "bias": jnp.zeros((E,), pd)},
            "layers": {
                "ln1_scale": jnp.ones((L, E), pd),
                "ln1_bias": jnp.zeros((L, E), pd),
                "ln2_scale": jnp.ones((L, E), pd),
                "ln2_bias": jnp.zeros((L, E), pd),
                "qkv_w": norm(next(k), (L, E, 3 * E), std),
                "qkv_b": jnp.zeros((L, 3 * E), pd),
                "proj_w": norm(next(k), (L, E, E), proj_std),
                "proj_b": jnp.zeros((L, E), pd),
            },
        }
        if cfg.moe_experts:
            X = cfg.moe_experts
            params["layers"].update(
                {
                    "router_w": norm(next(k), (L, E, X), std),
                    "expert_in": norm(next(k), (L, X, E, 4 * E), std),
                    "expert_out": norm(next(k), (L, X, 4 * E, E), proj_std),
                }
            )
        else:
            params["layers"].update(
                {
                    "mlp_in_w": norm(next(k), (L, E, 4 * E), std),
                    "mlp_in_b": jnp.zeros((L, 4 * E), pd),
                    "mlp_out_w": norm(next(k), (L, 4 * E, E), proj_std),
                    "mlp_out_b": jnp.zeros((L, E), pd),
                }
            )
        return params

    def param_pspecs(self, mesh=None) -> Dict[str, Any]:
        """PartitionSpecs over the standard mesh axes.  tp shards the
        contraction-free dim of each matmul (megatron column/row split);
        embeddings shard vocab on tp.

        fsdp (ZeRO-3-style) shards each layer's matrices on the dim tp
        leaves whole, never the stacked layer dim: the forward scans over
        that dim, and a slice along a sharded dim can only be served by
        gathering the whole stack on every iteration.  Sharded inside the
        layer, a layer's slice is that layer's shard, and `backbone` gathers
        it inside the checkpointed layer.  (That dim also keeps the float32
        shards compact on the TPU: `mlp_out_w` split on its rows pads 1600
        columns to 1664, 59 MB a chip at GPT-2 XL.)  The per-layer vectors
        replicate (their Adam moments are sharded all the same: lm_train's
        ZeRO-1 completion).  A matrix whose dim does not divide by the
        mesh's fsdp replicates over it: decided from the shapes.

        On a pp mesh the stacked layer dim is the *stage* dim: sharded over
        pp (one contiguous slice of layers per stage, consumed by the GPipe
        shard_map in backbone), and fsdp shards only the batch.  pp
        composes with dp/fsdp batch sharding AND with tp: the pipeline
        shard_map is manual over pp/dp/fsdp only, so tp-sharded layer
        weights keep compiler-managed in-stage collectives (shard_map
        manual-subset axes).  pp×sp (ring attention inside a manual region)
        is rejected up front."""
        shape = dict(mesh.shape) if mesh is not None else {}
        pp = shape.get("pp", 1) > 1
        if pp:
            if shape.get("sp", 1) > 1:
                raise NotImplementedError(
                    "pp composes with dp/fsdp (batch sharding) and tp; "
                    "pp×sp is not supported yet"
                )
            if shape.get("tp", 1) > 1 and self.config.pp_schedule == "1f1b":
                raise NotImplementedError("1f1b composes with dp/fsdp only")
        E = self.config.n_embd
        # the stacked dim: the stage dim under pp, else whole on every device
        L = "pp" if pp else None

        def fsdp(n):
            """Spec of a dim of size n that tp leaves whole: fsdp, where it
            divides and the mesh has no pp."""
            return None if pp or n % shape.get("fsdp", 1) else "fsdp"

        layers = {
            "ln1_scale": P(L, None),
            "ln1_bias": P(L, None),
            "ln2_scale": P(L, None),
            "ln2_bias": P(L, None),
            "qkv_w": P(L, fsdp(E), "tp"),
            "qkv_b": P(L, "tp"),
            "proj_w": P(L, "tp", fsdp(E)),
            "proj_b": P(L, None),
        }
        if self.config.moe_experts:
            # experts shard over ep on their expert dim
            layers.update(
                {
                    "router_w": P(L, fsdp(E), None),
                    "expert_in": P(L, "ep", fsdp(E), None),
                    "expert_out": P(L, "ep", fsdp(4 * E), None),
                }
            )
        else:
            layers.update(
                {
                    "mlp_in_w": P(L, fsdp(E), "tp"),
                    "mlp_in_b": P(L, "tp"),
                    "mlp_out_w": P(L, "tp", fsdp(E)),
                    "mlp_out_b": P(L, None),
                }
            )
        return {
            "wte": P("tp", None),
            "wpe": P(None, None),
            "ln_f": {"scale": P(None), "bias": P(None)},
            "layers": layers,
        }

    # ----------------------------------------------------------- forward

    def _layer(self, x: jax.Array, layer_params: Dict[str, jax.Array], mesh) -> jax.Array:
        cfg = self.config
        cd = cfg.compute_dtype
        B, S, E = x.shape
        H, D = cfg.n_head, cfg.head_dim

        from jax.ad_checkpoint import checkpoint_name

        def ln(h, scale, bias, name):
            h32 = h.astype(jnp.float32)
            mu = h32.mean(-1, keepdims=True)
            var = ((h32 - mu) ** 2).mean(-1, keepdims=True)
            out = ((h32 - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias).astype(cd)
            # named for the "lite" remat policy: recompute-from-residual
            # instead of saving the [B,S,E] buffer
            return checkpoint_name(out, name)

        h = ln(x, layer_params["ln1_scale"].astype(jnp.float32), layer_params["ln1_bias"].astype(jnp.float32), "ln1_out")
        qkv = h @ layer_params["qkv_w"].astype(cd) + layer_params["qkv_b"].astype(cd)
        q, k_, v_ = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, H, D)
        k_ = k_.reshape(B, S, H, D)
        v_ = v_.reshape(B, S, H, D)
        if cfg.use_ring_attention and mesh is not None and mesh.shape.get("sp", 1) > 1:
            # sequence parallelism: drop into SPMD-per-device code for the
            # attention only — the K/V ring rides ppermute over the sp axis
            import functools as _ft

            from ray_tpu.parallel.mesh import shard_map_compat
            from ray_tpu.parallel.ring_attention import ring_attention

            data = tuple(
                a for a in ("dp", "fsdp") if a in mesh.axis_names and mesh.shape[a] > 1
            )
            spec = jax.sharding.PartitionSpec(data or None, "sp", None, None)
            attn = shard_map_compat(
                _ft.partial(ring_attention, axis_name="sp", causal=True),
                mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )(q, k_, v_)
        else:
            attn = self._causal_attention(q, k_, v_, mesh)
        attn = attn.reshape(B, S, E)
        x = x + (attn @ layer_params["proj_w"].astype(cd) + layer_params["proj_b"].astype(cd))

        h = ln(x, layer_params["ln2_scale"].astype(jnp.float32), layer_params["ln2_bias"].astype(jnp.float32), "ln2_out")
        if cfg.moe_experts:
            x = x + self._moe_mlp(h, layer_params, mesh).astype(cd)
        else:
            h = h @ layer_params["mlp_in_w"].astype(cd) + layer_params["mlp_in_b"].astype(cd)
            h = checkpoint_name(jax.nn.gelu(h), "gelu_out")
            x = x + (h @ layer_params["mlp_out_w"].astype(cd) + layer_params["mlp_out_b"].astype(cd))
        return x

    def _moe_mlp(self, h: jax.Array, layer_params, mesh) -> jax.Array:
        """Top-1 switch MoE MLP: tokens all-to-all to their expert's device
        over the ep axis (parallel/moe.py).  ep==1 (or no mesh) runs the
        identical routed compute without collectives, so single-device and
        ep-sharded results agree at sufficient capacity."""
        import functools as _ft

        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.mesh import shard_map_compat
        from ray_tpu.parallel.moe import moe_ffn

        cfg = self.config
        cd = cfg.compute_dtype
        B, S, E = h.shape
        flat = h.reshape(B * S, E)
        router = layer_params["router_w"].astype(cd)
        ein = layer_params["expert_in"].astype(cd)
        eout = layer_params["expert_out"].astype(cd)
        fn = _ft.partial(
            moe_ffn, axis_name="ep", capacity_factor=cfg.moe_capacity_factor
        )
        if mesh is None:
            # degenerate ep group of one: same math, no collectives
            import numpy as _np

            from jax.sharding import Mesh

            mesh1 = Mesh(_np.array(jax.devices()[:1]), ("ep",))
            out = shard_map_compat(
                fn,
                mesh1,
                in_specs=(P(None), P(None), P(None), P(None)),
                out_specs=P(None),
            )(flat, router, ein, eout)
            return out.reshape(B, S, E)
        if "ep" not in mesh.axis_names:
            raise NotImplementedError(
                "MoE needs an ep axis on the mesh (keep_unit_axes meshes "
                "always carry one)"
            )
        data_axes = tuple(
            a for a in ("dp", "fsdp", "ep") if a in mesh.axis_names and mesh.shape[a] > 1
        )
        out = shard_map_compat(
            fn,
            mesh,
            in_specs=(
                P(data_axes or None, None),
                P(None, None),
                P("ep", None, None),
                P("ep", None, None),
            ),
            out_specs=P(data_axes or None, None),
        )(flat, router, ein, eout)
        return out.reshape(B, S, E)

    def _causal_attention(self, q, k, v, mesh=None):
        from ray_tpu.ops.attention import causal_attention

        return causal_attention(
            q,
            k,
            v,
            impl=self.config.attention_impl,
            scores_dtype=self.config.attn_scores_dtype,
            mesh=mesh,
        )

    def backbone(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        mesh=None,
    ) -> jax.Array:
        """tokens [B, S] int32 → final hidden states [B, S, E] in
        compute_dtype (post final layernorm, pre lm-head)."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, S = tokens.shape
        x = params["wte"].astype(cd)[tokens] + params["wpe"].astype(cd)[:S][None]

        if cfg.remat and cfg.remat_policy == "dots":
            # dots + the splash kernel's named residuals: saving the ~25MB
            # of attention output/lse per layer avoids re-running the whole
            # fwd attention kernel inside the backward pass.  (Also saving
            # ln/gelu outputs was measured SLOWER — their recompute is
            # cheaper than the extra HBM round-trips.)
            policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names("splash_residuals"),
            )
        elif cfg.remat and cfg.remat_policy == "lite":
            policy = jax.checkpoint_policies.save_anything_except_these_names(
                "ln1_out", "ln2_out", "gelu_out"
            )
        else:
            policy = None

        # the fsdp gather is stated, not left to the partitioner's cost model,
        # which may as well keep the weights sharded and gather the BATCH
        # (tensor parallelism over fsdp: the 48-layer step then does not
        # fit).  `whole`: each sharded matrix's layout once gathered; empty
        # unless the mesh's fsdp is > 1, so a one-chip program is untouched.
        whole = {}
        if mesh is not None:
            for name, spec in self.param_pspecs(mesh)["layers"].items():
                gathered = prune_pspec(spec, mesh, drop=("fsdp",))
                if gathered != prune_pspec(spec, mesh):
                    whole[name] = NamedSharding(mesh, P(*gathered[1:]))

        def layer(x, layer_params):
            # cast, then gather: half the bytes on the links.  Inside the
            # checkpoint: no gathered weight is a residual of every layer,
            # the backward gathers a layer again as it recomputes it and
            # reduces that layer's gradient back to its shard.
            lp = {
                k: jax.lax.with_sharding_constraint(v.astype(cd), whole[k]) if k in whole else v
                for k, v in layer_params.items()
            }
            return self._layer(x, lp, mesh)

        def scan_body(x, layer_params):
            if cfg.remat:
                y = jax.checkpoint(layer, policy=policy)(x, layer_params)
            else:
                y = layer(x, layer_params)
            return y, None

        if mesh is not None and dict(mesh.shape).get("pp", 1) > 1:
            # GPipe over the pp axis: each stage scans its layer slice,
            # activations hop stage→stage by ppermute (parallel/pipeline.py)
            from ray_tpu.parallel.pipeline import make_pipeline

            def stage_fn(stage_layers, h):
                out, _ = jax.lax.scan(scan_body, h, stage_layers)
                return out

            pipe = make_pipeline(
                mesh,
                stage_fn,
                num_microbatches=cfg.pp_microbatches,
                batch_axes=("dp", "fsdp"),
            )
            x = pipe(params["layers"], x)
        else:
            x, _ = jax.lax.scan(scan_body, x, params["layers"])
        scale = params["ln_f"]["scale"].astype(jnp.float32)
        bias = params["ln_f"]["bias"].astype(jnp.float32)
        x32 = x.astype(jnp.float32)
        mu = x32.mean(-1, keepdims=True)
        var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
        x = (x32 - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias
        return x.astype(cd)

    def apply(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        mesh=None,
    ) -> jax.Array:
        """tokens [B, S] int32 → logits [B, S, padded_vocab].

        Stays in bf16: the naive loss upcasts inside fused reductions —
        returning f32 here would materialize an extra [B,S,V] f32 tensor."""
        x = self.backbone(params, tokens, mesh)
        return x @ params["wte"].astype(self.config.compute_dtype).T

    def loss_and_grads_1f1b(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        targets: jax.Array,
        mesh,
    ):
        """(loss, grads) via the explicit 1F1B pipeline schedule
        (parallel/pipeline.py pipeline_train_1f1b): embedding runs at
        stage 0, the final-norm + tied-head CE at the last stage, each
        per-microbatch — gradients match the GPipe/sequential path while
        live activations stay bounded by the pipe depth.  Composes with
        dp/fsdp batch sharding; tp/sp/ep under 1F1B are rejected."""
        import functools as _ft

        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.mesh import shard_map_compat
        from ray_tpu.parallel.pipeline import pipeline_train_1f1b

        cfg = self.config
        cd = cfg.compute_dtype
        shape = dict(mesh.shape)
        if shape.get("tp", 1) > 1 or shape.get("sp", 1) > 1 or shape.get("ep", 1) > 1:
            raise NotImplementedError("1f1b composes with dp/fsdp only")
        pp = shape["pp"]
        batch_axes = tuple(
            a for a in ("dp", "fsdp") if a in mesh.axis_names and mesh.shape[a] > 1
        )

        def embed_fn(extra, tok_mb):
            S = tok_mb.shape[1]
            return extra["wte"].astype(cd)[tok_mb] + extra["wpe"].astype(cd)[:S][None]

        def stage_fn(stage_layers, h):
            def scan_body(x, layer_params):
                if cfg.remat:
                    y = jax.checkpoint(lambda x_, lp: self._layer(x_, lp, None))(
                        x, layer_params
                    )
                else:
                    y = self._layer(x, layer_params, None)
                return y, None

            out, _ = jax.lax.scan(scan_body, h, stage_layers)
            return out

        def loss_fn(extra, y, tgt_mb):
            scale = extra["ln_f"]["scale"].astype(jnp.float32)
            bias = extra["ln_f"]["bias"].astype(jnp.float32)
            x32 = y.astype(jnp.float32)
            mu = x32.mean(-1, keepdims=True)
            var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
            h = ((x32 - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias).astype(cd)
            logits = (h @ extra["wte"].astype(cd).T).astype(jnp.float32)
            if cfg.padded_vocab != cfg.vocab_size:
                pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
                logits = jnp.where(pad_mask, -1e30, logits)
            label_logit = jnp.take_along_axis(logits, tgt_mb[..., None], axis=-1)[..., 0]
            lse = jax.nn.logsumexp(logits, axis=-1)
            return (lse - label_logit).mean()

        def body(stage_layers, extra, tok_l, tgt_l):
            B = tok_l.shape[0]
            M = max(
                d
                for d in range(1, min(cfg.pp_microbatches, B) + 1)
                if B % d == 0
            )
            tok_mbs = tok_l.reshape(M, B // M, *tok_l.shape[1:])
            tgt_mbs = tgt_l.reshape(M, B // M, *tgt_l.shape[1:])
            loss, sg, eg = pipeline_train_1f1b(
                stage_layers,
                extra,
                tok_mbs,
                tgt_mbs,
                stage_fn=stage_fn,
                embed_fn=embed_fn,
                loss_fn=loss_fn,
                reduce_axes=batch_axes,
            )
            return loss, sg, eg

        def layer_spec(leaf):
            return P("pp", *([None] * (leaf.ndim - 1)))

        extra_params = {k: v for k, v in params.items() if k != "layers"}
        layer_specs = jax.tree.map(layer_spec, params["layers"])
        extra_specs = jax.tree.map(lambda _: P(), extra_params)
        data_spec = P(batch_axes or None, None)

        loss, sg, eg = shard_map_compat(
            body,
            mesh,
            in_specs=(layer_specs, extra_specs, data_spec, data_spec),
            out_specs=(P(), layer_specs, extra_specs),
        )(params["layers"], extra_params, tokens, targets)
        grads = dict(eg)
        grads["layers"] = sg
        return loss, grads

    def loss(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        targets: jax.Array,
        mesh=None,
    ) -> jax.Array:
        """Mean next-token cross entropy; padded-vocab tail masked out.

        Default ("auto"/"fused") path: chunked linear-head CE — the [B,S,V]
        logits tensor never exists in HBM (ops/cross_entropy.py; the single
        biggest HBM consumer of the naive form).  "naive" keeps the
        full-logits path for layouts the chunked scan can't express
        (sequence axis sharded by sp ring attention)."""
        cfg = self.config
        impl = cfg.loss_impl
        if impl == "auto":
            sp = mesh is not None and mesh.shape.get("sp", 1) > 1
            if sp:
                impl = "naive"  # chunked scan can't express the sp layout
            else:
                # naive materializes the [B,S,V] logits (f32): faster when
                # it fits (no bwd recompute — measured 162 vs 174 ms at
                # b16/v5e), deadly when it doesn't.  Estimate the
                # PER-DEVICE footprint against a 4 GiB budget.
                shards = 1
                if mesh is not None:
                    for a in ("dp", "fsdp"):
                        shards *= dict(mesh.shape).get(a, 1)
                B, S = tokens.shape
                f32_bytes = B * S * cfg.padded_vocab * 4 // max(1, shards)
                impl = "naive" if f32_bytes <= (4 << 30) else "fused"
        if impl == "fused":
            from ray_tpu.ops.cross_entropy import fused_linear_cross_entropy

            x = self.backbone(params, tokens, mesh)
            w = params["wte"].astype(cfg.compute_dtype)
            chunk = cfg.loss_chunk or max(128, min(512, 8192 // max(1, tokens.shape[0])))
            return fused_linear_cross_entropy(
                x, w, targets, cfg.vocab_size, chunk
            )
        logits = self.apply(params, tokens, mesh).astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            # select (fuses into the logsumexp reduction) instead of a
            # scatter, which would materialize a full [B,S,V] copy
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad_mask, -1e30, logits)
        label_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        lse = jax.nn.logsumexp(logits, axis=-1)
        return (lse - label_logit).mean()
