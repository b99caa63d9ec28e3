"""Compile the serving engine's two programs for the v5e WITHOUT a chip, at a
benchmark configuration's published widths, and read the optimised HLO.

``jax.experimental.topologies.get_topology_desc(platform="tpu", ...)`` gives
compile-only devices (libtpu is installed here; no device is opened);
``ShardedLLM(init="abstract")`` gives the parameter tree as
``ShapeDtypeStruct``s with the replica's shardings, and ``engine_programs`` the
replica's own two ``jax.jit`` objects, lowered with abstract arguments at the
cell's shapes.  Used by ``tests/test_weight_copies.py``; run as a script it
prints the readings of one configuration (``python tests/_aot_v5e.py
mistral-7b-l16 3``)."""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
from typing import Dict, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PROGRAMS = ("decode", "prefill")
BIG = 4 << 20  # a weight-sized result: 4 MB


def topology_devices():
    """The v5e 2x2 host's compile-only devices, or a string saying why not."""
    try:
        from jax.experimental import topologies

        return list(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices)
    except Exception as e:  # noqa: BLE001 -- no libtpu, or one that cannot describe a topology: the caller skips
        return f"get_topology_desc unavailable: {type(e).__name__}: {e}"[:300]


def load_config(name: str, n_layers: int):
    """(the program's config at the file's widths and ``n_layers``, the file's engine geometry)."""
    from benchmarks.drivers import serve, serve_conv_moe, serve_jamba, serve_mla_moe, serve_moe, serve_qwen3_next

    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    if cfg["kind"] == "serve_jamba":  # a few layers of BOTH kinds: the file's own list, not the first ``n_layers``
        lcfg = serve_jamba.reference_config(cfg)
        assert lcfg.n_layers == n_layers, (lcfg.n_layers, n_layers)
        return lcfg, cfg["engine"]
    build = {"serve": serve.llama_config, "serve_moe": serve_moe.moe_config, "serve_qwen3_next": serve_qwen3_next.hybrid_config,
             "serve_mla_moe": serve_mla_moe.mla_config, "serve_conv_moe": serve_conv_moe.conv_config}[cfg["kind"]]
    return dataclasses.replace(build(cfg), n_layers=n_layers), cfg["engine"]


def compile_programs(lcfg, engine: dict, devices, with_memory: bool = False) -> Dict[str, Tuple]:
    """{"decode" | "prefill": (optimised HLO text, cost_analysis bytes accessed)}
    of ``ShardedLLM(lcfg).engine_programs`` over ``devices[:1]`` at the
    engine geometry of the configuration's file; ``with_memory``: and the
    compiler's ``memory_analysis()`` (arguments, temporaries) as a third."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm import ShardedLLM

    llm = ShardedLLM(lcfg, devices=devices[:1], init="abstract")
    slots, page, chunk = int(engine["num_slots"]), int(engine["page_size"]), int(engine["prefill_chunk"])
    per_slot = int(engine["max_seq_len"]) // page
    programs = llm.engine_programs(num_pages=int(engine.get("num_pages") or slots * per_slot), page_size=page, num_slots=slots)
    repl = jax.sharding.NamedSharding(llm.mesh, jax.sharding.PartitionSpec())
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=repl)  # noqa: E731
    pool = tuple(sds(a.shape, a.dtype) for a in jax.eval_shape(programs["init"]))
    i32 = jnp.int32
    args = {
        # as the engine calls it (loop.py): the frontier on the device, and the slot and token of the row that joins
        "decode": (llm.params, pool, sds((slots, per_slot), i32), sds((slots,), i32), sds((slots,), i32), sds((slots,), jnp.bool_), sds((), i32), sds((), i32)),
        # the engine passes the chunk's slot to every model (loop.py)
        "prefill": (llm.params, pool, sds((per_slot,), i32), sds((chunk,), i32), sds((), i32), sds((), i32), sds((), i32)),
    }
    out = {}
    for name in PROGRAMS:
        compiled = programs[name].lower(*args[name]).compile()
        out[name] = (compiled.as_text(), float(compiled.cost_analysis()["bytes accessed"])) + ((compiled.memory_analysis(),) if with_memory else ())
    return out


def without_barrier():
    """Context: ``jax.lax.optimization_barrier`` is the identity, so
    ``LlamaModel._qkv`` traces as it did before it had one."""
    from unittest import mock

    import jax

    return mock.patch.object(jax.lax, "optimization_barrier", lambda x: x)


# ---- reading the entry computation

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(_DTYPE_BYTES))


def _computations(hlo: str) -> Dict[str, str]:
    """name -> body text, of every computation in the module."""
    out, name, body = {}, None, []
    for ln in hlo.splitlines():
        m = re.match(r"\s*(ENTRY\s+)?%?([\w.\-]+)\s*(\([^)]*\))?\s*->.*\{\s*$", ln)
        if m and name is None:
            name, body = ("ENTRY" if m.group(1) else m.group(2)), []
        elif name is not None and ln.strip() == "}":
            out[name], name = "\n".join(body), None
        elif name is not None:
            body.append(ln)
    return out


def device_ops_named(hlo: str, prefix: str) -> list:
    """Names of the instructions ``prefix*`` that run as device operations
    of their own: those outside fused computations (one fused INTO a matmul
    is read in place and writes nothing)."""
    return [
        m.group(1)
        for comp, body in _computations(hlo).items() if not comp.startswith("fused_computation")
        for m in re.finditer(r"^\s*(?:ROOT\s+)?%%?(%s[.\d]*) = " % re.escape(prefix), body, re.M)
    ]


def weight_relayouts(hlo: str) -> list:
    """Instructions of the ENTRY computation that copy or re-lay a weight
    on every call: synchronous (no ``*-start``/``*-done`` prefetch into
    faster memory, which overlaps), not a matmul, reading a parameter of the
    ``params`` tree (directly or through bitcasts) and writing ``BIG`` bytes
    or more.  [(name, result, bytes)]."""
    comps = _computations(hlo)
    weights, found = set(), []
    for ln in comps.get("ENTRY", "").splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)", ln)
        if not m:
            continue
        name, result, opcode, rest = m.groups()
        operands = set(re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0]))
        if opcode == "parameter":
            if name.startswith("params_"):
                weights.add(name)
            continue
        if not operands & weights:
            continue
        if opcode == "bitcast":
            weights.add(name)
        if opcode not in ("fusion", "copy", "transpose"):
            continue
        called = re.search(r"calls=%?([\w.\-]+)", ln)
        if called and re.search(r" (convolution|dot)\(", comps.get(called.group(1), "")):
            continue
        sizes = []
        for dt, dims in _SHAPE.findall(result):
            nbytes = _DTYPE_BYTES[dt]
            for d in dims.split(","):
                nbytes *= int(d) if d else 1
            sizes.append(nbytes)
        if sizes and max(sizes) >= BIG:
            found.append((name, re.sub(r"\{[^{}]*\}", "", result)[:120], sum(sizes)))
    return found


def array_shapes(hlo: str) -> set:
    """Every array shape the module's text names: {(dtype, (dims...))}."""
    return {(dt, tuple(int(d) for d in dims.split(",") if d)) for dt, dims in _SHAPE.findall(hlo)}


def readings(name: str, n_layers: int, devices) -> dict:
    import contextlib

    lcfg, engine = load_config(name, n_layers)
    out = {"config": name, "layers": n_layers}
    for label, ctx in (("as_written", contextlib.nullcontext()), ("no_barrier", without_barrier())):
        with ctx:
            compiled = compile_programs(lcfg, engine, devices)
        for prog, (hlo, nbytes) in compiled.items():
            out[f"{prog}.{label}"] = {
                "bytes_accessed": nbytes,
                "slice_bitcast_fusions": len(device_ops_named(hlo, "slice_bitcast_fusion")),
                "weight_relayouts": [(n, b) for n, _, b in weight_relayouts(hlo)],
            }
    return out


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    devs = topology_devices()
    if isinstance(devs, str):
        sys.exit(devs)
    for key, val in readings(sys.argv[1], int(sys.argv[2]), devs).items():
        print(key, json.dumps(val))
