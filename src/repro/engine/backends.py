"""Backend registry — every index build and query pass dispatches here.

A :class:`Backend` pairs the two primitive operations the engine needs:

  * ``create_index(records (N, W) int, keys (M,) int)``
      -> key-major packed bitmap (M, ceil(N/32)) uint32, with all pad bits
      past N guaranteed zero (the canonical sentinel policy ensures padded
      records match nothing).  Records may arrive narrowed (uint8 or
      uint16, as ``BitmapDB.append_encoded`` uploads host blocks whose
      words fit) and are widened to int32 on the device;
  * ``query(rows (K, Nw) uint32, invert (K,) int)``
      -> (result row (Nw,) uint32, popcount) for AND_k (invert_k ? ~r : r).
      Tail bits past the logical record count are NOT masked here — the
      planner applies :func:`repro.engine.policy.mask_tail` exactly once per
      compiled plan.

A backend may additionally provide ``run_program`` — a whole-bucket
executor with the batched layer's call contract (augmented index, record
count, ``(Q, G, P, L)`` selector arrays, post xor masks -> rows + counts).
When present, :mod:`repro.engine.batch` jits IT as the bucket executor
instead of composing per-pass ``query`` calls — the hook a bulk-bitwise
path needs to fuse a whole pass program into one multi-word sweep.

Built-ins: ``pallas`` (the TPU kernels; interpret mode off-TPU), ``ref``
(the pure-jnp oracle) and ``bulk`` (the tiled bulk-bitwise sweep of
:mod:`repro.engine.bulk` — Pallas word-tiled kernel on TPU, pure-jnp tile
sweep elsewhere).  ``auto`` without workload information resolves to
``pallas`` on TPU and ``ref`` elsewhere — vmapping interpreted Pallas
kernels on CPU is strictly slower than the oracle; the workload-aware
call sites (``planner.execute``, ``engine.batch``, ``repro.db``) instead
route ``auto`` through the measured cost model
(:mod:`repro.engine.costmodel`).  New backends (e.g. a future GPU or
bit-sliced CPU path) register with :func:`register_backend`.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Protocol

import jax

from repro.engine import bulk, policy
from repro.kernels import ops, ref


class _CreateFn(Protocol):
    def __call__(self, records: jax.Array, keys: jax.Array) -> jax.Array: ...


class _QueryFn(Protocol):
    def __call__(self, rows: jax.Array, invert: jax.Array
                 ) -> tuple[jax.Array, jax.Array]: ...


class _ProgramFn(Protocol):
    def __call__(self, aug: jax.Array, num_records, sels: jax.Array,
                 invs: jax.Array, post: jax.Array
                 ) -> tuple[jax.Array, jax.Array]: ...


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    create_index: _CreateFn
    query: _QueryFn
    #: optional whole-bucket executor (see module docstring); backends
    #: without one get the per-pass bucket body composed around ``query``
    run_program: _ProgramFn | None = None


_REGISTRY: dict[str, Backend] = {}


# Compiled executors (sequential, factored, batched, stacked, vmapped-
# create) close over Backend objects; re-registering a name must drop them
# so stale backends never keep serving.  getattr-guarded: a module may be
# mid-import.
_COMPILED_CACHES = (
    ("repro.engine.planner", ("_compiled", "_compiled_factored")),
    ("repro.engine.batch", ("_executor", "_stacked_executor")),
    ("repro.engine.runtime", ("_vmapped_create",)),
)


def register_backend(backend: Backend) -> Backend:
    _REGISTRY[backend.name] = backend
    for modname, attrs in _COMPILED_CACHES:
        mod = sys.modules.get(modname)
        for attr in attrs if mod is not None else ():
            cache = getattr(mod, attr, None)
            if cache is not None:
                cache.cache_clear()
    return backend


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY)) + ("auto",)


def resolve_backend(name: str) -> str:
    """Map ``auto`` to a concrete backend for the current jax platform."""
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if name not in _REGISTRY:
        raise ValueError(f"unknown backend {name!r}; "
                         f"registered: {available_backends()}")
    return name


def get_backend(name: str = "auto") -> Backend:
    return _REGISTRY[resolve_backend(name)]


# ------------------------------------------------------------ built-ins
def _ref_create_index(records: jax.Array, keys: jax.Array) -> jax.Array:
    """Oracle path: pad to PACK multiples with the canonical sentinels, run
    the pure-jnp pipeline, slice back to logical shape."""
    n = records.shape[0]
    m = keys.shape[0]
    packed = ref.create_index(policy.pad_records(records),
                              policy.pad_keys(keys))
    return packed[:m, : policy.num_words(n)]


register_backend(Backend("ref", _ref_create_index, ref.bitmap_query))
register_backend(Backend("pallas", ops.create_index, ops.query))
register_backend(Backend("bulk", bulk.create_index, bulk.query,
                         run_program=bulk.run_program))
