"""Multiprocess shard workers: one ``BitmapDB`` + ``BitmapService`` +
socket server per spawned process.

:func:`spawn_shards` launches N workers (``multiprocessing`` spawn
context — each child is a fresh interpreter that imports jax on its own)
and returns a :class:`ShardFleet` with their bound addresses; the parent
then builds a :class:`~repro.fabric.client.FabricClient` over them with
``FabricClient.connect``.  Each worker:

  * opens its store (``store_path`` with a committed manifest resumes
    it; a bare path creates a durable store; neither -> in-memory) and
    optionally ingests a records array handed to it at spawn;
  * optionally installs a JSONL-sink :class:`~repro.obs.trace.Tracer`
    and, on shutdown, writes ``shard-<id>-health.json`` /
    ``shard-<id>-metrics.json`` — the per-shard artifacts the CI
    fabric-smoke job uploads;
  * serves until a ``shutdown`` envelope arrives (the fleet's
    ``close()`` sends one per worker, then joins with a terminate
    fallback so a wedged worker cannot hang the parent).

One process per chip: on a TPU host only one process may hold the chips,
so N workers that each import JAX would contend for them.
:func:`spawn_shards` therefore refuses to run where its children would
claim a TPU (decided from the environment, without importing JAX in the
parent); one process drives every chip through
``FabricClient.local`` with each shard's ``BitmapDB`` on its own device.
"""
from __future__ import annotations

import glob
import json
import multiprocessing as mp
import os
import threading

import numpy as np

__all__ = ["ShardFleet", "spawn_shards"]


def _shard_main(conn, shard_id: int, store_path: str | None,
                schema_text: str | None, num_keys: int | None,
                records: np.ndarray | None, config_kw: dict,
                artifact_dir: str | None) -> None:
    """Worker entrypoint (spawn target — top-level and import-light
    until inside, so child startup stays cheap)."""
    from repro import db as db_mod
    from repro.db.schema import Schema
    from repro.fabric.protocol import ServiceHost
    from repro.fabric.transport import serve_socket
    from repro.obs import trace as obs_trace
    from repro.serve.service import BitmapService, ServiceConfig
    from repro.store import format as fmt

    tracer = None
    sink_f = None
    if artifact_dir:
        os.makedirs(artifact_dir, exist_ok=True)
        sink_f = open(os.path.join(artifact_dir,
                                   f"shard-{shard_id}-trace.jsonl"),
                      "w", buffering=1)

        def sink(d, _f=sink_f):
            _f.write(json.dumps(d) + "\n")

        tracer = obs_trace.install(obs_trace.Tracer(sink=sink))

    schema = Schema.from_json(schema_text) if schema_text else None
    if store_path and os.path.exists(os.path.join(store_path, "CURRENT")):
        session = db_mod.BitmapDB.open(store_path, num_keys=num_keys)
    elif store_path:
        session = db_mod.BitmapDB(schema, num_keys=num_keys,
                                  path=store_path)
    else:
        session = db_mod.BitmapDB(schema, num_keys=num_keys)
    if records is not None and records.shape[0]:
        session.append_encoded(records)

    service = BitmapService(session, ServiceConfig(**config_kw))
    done = threading.Event()
    host = ServiceHost(service, shard_id=shard_id,
                       on_shutdown=done.set)
    server = serve_socket(host)
    conn.send(("ready", server.address))
    conn.close()
    try:
        done.wait()
    finally:
        if artifact_dir:
            try:
                # atomic + seamed (format.write): a fault plan can tear
                # or drop these exactly like any other durable artifact
                fmt.write_json_atomic(
                    os.path.join(artifact_dir,
                                 f"shard-{shard_id}-metrics.json"),
                    _jsonable(service.metrics().to_dict()))
                fmt.write_json_atomic(
                    os.path.join(artifact_dir,
                                 f"shard-{shard_id}-health.json"),
                    _jsonable(service.health()))
            except Exception:           # noqa: BLE001 — artifacts only
                pass
        server.close()
        host.close()
        if tracer is not None:
            obs_trace.uninstall(tracer)
        if sink_f is not None:
            sink_f.close()


def _jsonable(obj):
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.generic):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, float) and obj != obj:
        return None
    return obj


class ShardFleet:
    """Handle to a set of spawned shard workers."""

    def __init__(self, procs, addresses):
        self.procs = procs
        self.addresses: list[tuple[str, int]] = addresses
        self._closed = False

    def __enter__(self) -> "ShardFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self, timeout: float = 30.0) -> None:
        """Ask every worker to shut down (a ``shutdown`` envelope over a
        short-lived connection), then join; terminate stragglers."""
        if self._closed:
            return
        self._closed = True
        from repro.fabric.envelope import Envelope
        from repro.fabric.transport import SocketTransport
        for addr in self.addresses:
            try:
                t = SocketTransport(addr, connect_timeout=2.0)
                try:
                    t.request(Envelope("shutdown"), timeout=5.0)
                finally:
                    t.close()
            except OSError:
                pass                    # already gone
        for p in self.procs:
            p.join(timeout=timeout / max(len(self.procs), 1))
        for p in self.procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)


#: a TPU chip on the PCI bus: Google's vendor id, "processing
#: accelerator" class (Google's virtual NICs share the vendor id)
_GOOGLE_PCI_VENDOR = "0x1ae0"
_ACCELERATOR_PCI_CLASS = "0x12"


def _pci_attr(dev: str, name: str) -> str:
    try:
        with open(os.path.join(dev, name)) as f:
            return f.read().strip()
    except OSError:
        return ""


def children_would_claim_tpu() -> bool:
    """Whether a spawned child that imports JAX would try to take a TPU.
    Decided without touching JAX in this process: the child inherits
    ``JAX_PLATFORMS`` when it is set; otherwise JAX claims a TPU whenever
    the host exposes one (``/dev/accel*``, or a Google accelerator on the
    PCI bus)."""
    platforms = os.environ.get("JAX_PLATFORMS", "").strip()
    if platforms:
        return "tpu" in platforms.lower().split(",")
    if glob.glob("/dev/accel*"):
        return True
    return any(_pci_attr(dev, "vendor") == _GOOGLE_PCI_VENDOR
               and _pci_attr(dev, "class").startswith(_ACCELERATOR_PCI_CLASS)
               for dev in glob.glob("/sys/bus/pci/devices/*"))


def spawn_shards(num_shards: int, *, schema=None, num_keys=None,
                 store_paths=None, shard_records=None,
                 service_config: dict | None = None,
                 artifact_dir: str | None = None,
                 start_timeout_s: float = 120.0) -> ShardFleet:
    """Launch ``num_shards`` worker processes and wait for their bound
    addresses.  ``shard_records`` (optional) is one encoded ``(N, W)``
    int32 array per shard, ingested before the worker reports ready —
    the parent typically produced it with ``ShardMap.partition`` and
    keeps the matching gid tables for its client.

    Raises RuntimeError where the workers would claim a TPU (see
    :func:`children_would_claim_tpu`): a chip host runs its shards in one
    process instead (``FabricClient.local``)."""
    if children_would_claim_tpu():
        raise RuntimeError(
            "spawn_shards starts one JAX process per shard, but on a TPU "
            "host one process holds the chips; serve the shards from one "
            "process with FabricClient.local, each BitmapDB on its own "
            "device (BitmapDB(..., device=jax.devices()[i]))")
    ctx = mp.get_context("spawn")
    schema_text = schema.to_json() if schema is not None else None
    procs, conns = [], []
    for sid in range(num_shards):
        parent, child = ctx.Pipe()
        recs = None if shard_records is None else \
            np.asarray(shard_records[sid], np.int32)
        sp = None if store_paths is None else store_paths[sid]
        p = ctx.Process(
            target=_shard_main,
            args=(child, sid, sp, schema_text, num_keys, recs,
                  dict(service_config or {}), artifact_dir),
            name=f"repro-shard-{sid}", daemon=True)
        p.start()
        child.close()
        procs.append(p)
        conns.append(parent)
    addresses = []
    try:
        for sid, conn in enumerate(conns):
            if not conn.poll(start_timeout_s):
                raise TimeoutError(f"shard {sid} did not come up within "
                                   f"{start_timeout_s}s")
            tag, addr = conn.recv()
            if tag != "ready":
                raise RuntimeError(f"shard {sid} failed to start: "
                                   f"{addr}")
            addresses.append(tuple(addr))
            conn.close()
    except BaseException:
        for p in procs:
            p.terminate()
        raise
    return ShardFleet(procs, addresses)
