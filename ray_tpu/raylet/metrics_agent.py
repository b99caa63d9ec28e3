"""Per-node metrics agent: a Prometheus scrape endpoint on every node.

Analog of the reference's per-node reporter agent (reference:
dashboard/modules/reporter/reporter_agent.py — psutil node stats +
_private/metrics_agent.py:63 Prometheus export).  Each raylet (and the
head, for its own node) serves ``/metrics`` with node CPU/memory, object
store occupancy, JAX device gauges (HBM used/total via
``device.memory_stats()``, device count/kind), and the cluster's
application metrics (ray_tpu.util.metrics registry, including the
flight-recorder phase histograms) — so a stock Prometheus scrape_config
covers scheduler health AND TPU memory pressure node-by-node.
"""

from __future__ import annotations

import inspect
import os
import sys
from typing import Callable, Optional


def _node_stats_text(node_id_hex: str, store=None) -> str:
    import psutil

    tags = f'{{NodeId="{node_id_hex}"}}'
    lines = []

    def emit(name, kind, value, help_text):
        lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {kind}")
        lines.append(f"{name}{tags} {value}")

    emit("node_cpu_percent", "gauge", psutil.cpu_percent(interval=None),
         "CPU utilization of this node (percent)")
    vm = psutil.virtual_memory()
    emit("node_mem_used_bytes", "gauge", vm.used, "Used node memory")
    emit("node_mem_total_bytes", "gauge", vm.total, "Total node memory")
    try:
        la1, la5, la15 = __import__("os").getloadavg()
        emit("node_load1", "gauge", la1, "1-minute load average")
    except OSError:
        pass
    if store is not None:
        emit("object_store_used_bytes", "gauge", store.used(),
             "Bytes allocated in this node's shm object store")
        emit("object_store_capacity_bytes", "gauge", store.capacity(),
             "Capacity of this node's shm object store")
        emit("object_store_num_objects", "gauge", store.num_objects(),
             "Objects resident in this node's shm store")
        emit("object_store_evictions_total", "counter", store.evictions(),
             "LRU evictions since store creation")
    return "\n".join(lines) + "\n"


def _jax_probe_allowed() -> bool:
    """May this process touch jax.devices()?  The call initialises a
    backend, which on a TPU host takes every chip (_private/tpu.py), and the
    agent lives in head/raylet processes that must never take them from the
    worker that owns them.  Probe only when it cannot (explicit CPU
    backend), when jax is already resident in this process, or when the
    operator opted in with RAY_TPU_DEVICE_METRICS=1."""
    flag = os.environ.get("RAY_TPU_DEVICE_METRICS", "").strip().lower()
    if flag in ("0", "false", "no", "off"):
        return False
    if flag:
        return True
    if "jax" in sys.modules:
        return True
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def _device_stats_text(node_id_hex: str) -> str:
    """JAX device gauges: count/kind always, HBM used/total per device
    where the backend reports memory_stats (TPU; CPU devices return None).
    Family # TYPE headers are emitted even when a backend yields no
    memory samples, so scrapers always see the families."""
    if not _jax_probe_allowed():
        return ""
    try:
        import jax

        devices = jax.devices()
    except Exception:  # graftlint: disable=silent-except -- no usable jax backend in this process; node stats still serve
        return ""
    lines = [
        "# HELP jax_device_count JAX-visible devices on this node",
        "# TYPE jax_device_count gauge",
        f'jax_device_count{{NodeId="{node_id_hex}"}} {len(devices)}',
        "# HELP jax_device_hbm_used_bytes Device memory in use"
        " (device.memory_stats bytes_in_use)",
        "# TYPE jax_device_hbm_used_bytes gauge",
    ]
    used_lines, total_lines = [], []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # graftlint: disable=silent-except -- backend without memory introspection; count/kind gauges still serve
            stats = None
        labels = (
            f'{{NodeId="{node_id_hex}",device="{d.id}",kind="{d.device_kind}"}}'
        )
        if not stats:
            continue
        if "bytes_in_use" in stats:
            used_lines.append(
                f"jax_device_hbm_used_bytes{labels} {stats['bytes_in_use']}"
            )
        limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
        if limit:
            total_lines.append(
                f"jax_device_hbm_total_bytes{labels} {limit}"
            )
    lines.extend(used_lines)
    lines.append(
        "# HELP jax_device_hbm_total_bytes Device memory capacity"
        " (device.memory_stats bytes_limit)"
    )
    lines.append("# TYPE jax_device_hbm_total_bytes gauge")
    lines.extend(total_lines)
    return "\n".join(lines) + "\n"


async def start_metrics_server(
    node_id_hex: str,
    store=None,
    port: int = 0,
    app_metrics: Optional[Callable[[], object]] = None,
) -> int:
    """Serve /metrics on this node; returns the bound port.

    ``app_metrics`` supplies the application-metrics section as
    Prometheus text (sync or async callable): the head passes a renderer
    over its own kv table, raylets pass an async reader that pulls the
    metrics records from the head.  Without it, the legacy in-process
    fallback (a connected worker's prometheus_text) is attempted."""
    import asyncio

    from aiohttp import web

    from ray_tpu.util import metrics as metrics_mod

    async def handle(_request):
        body = _node_stats_text(node_id_hex, store)
        # first device probe may import jax (seconds): keep the event loop
        # serving — the head's RPC loop shares it
        body += await asyncio.get_running_loop().run_in_executor(
            None, _device_stats_text, node_id_hex
        )
        try:
            if app_metrics is not None:
                out = app_metrics()
                if inspect.isawaitable(out):
                    out = await out
                body += out or ""
            else:
                # app metrics live in the cluster KV: only reachable from a
                # connected process (a bare agent serves node stats only).
                # Off-loop: the read is a sync RPC to the head, and this
                # loop may be the head's own RPC loop.
                body += await asyncio.get_running_loop().run_in_executor(
                    None, metrics_mod.prometheus_text
                )
        except Exception:  # graftlint: disable=silent-except -- app-metrics source unavailable (disconnected agent / head mid-restart); node+device stats still serve, by design
            pass
        return web.Response(text=body, content_type="text/plain")

    app = web.Application()
    app.router.add_get("/metrics", handle)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "0.0.0.0", port)
    await site.start()
    return site._server.sockets[0].getsockname()[1]
