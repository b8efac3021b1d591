"""AdapterBank: many LoRA adapters resident on device as one stacked tree.

S-LoRA/Punica-style multi-tenant serving: the bank holds ``max_adapters``
rank-padded adapters stacked on a leading axis (``a: [M, in, R]``,
``b: [M, R, out]``, ``scale: [M]``), so the engine's compiled forward can
gather any slot's adapter with a plain index — *membership is data*. Row 0
is reserved as the identity (all-zero) adapter for base-model requests;
its delta is exactly ``0.0``, so base requests through a bank-equipped
engine produce the same tokens as the bare engine.

The host side is a named registry with LRU residency. ``acquire`` pins a
named adapter into a row (loading/evicting via one pre-compiled
``dynamic_update_slice`` row write — the bank's shape never changes, so no
executable is ever recompiled); ``release`` unpins it when the request
retires. All bookkeeping is lock-protected: ``register``/lookups come from
caller threads while ``acquire``/``release`` run on the engine thread.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .lora import (
    LoRAConfig,
    adapter_module_paths,
    adapter_rank,
    pad_adapter,
    target_paths,
    _get_path,
    _set_path,
)


class UnknownAdapterError(LookupError):
    """Request names an adapter nobody registered (HTTP 404 at the gateway)."""


class AdapterBankFull(RuntimeError):
    """Every bank row is pinned by an in-flight request — retry later.

    Deliberately *not* an engine fault: the engine stays healthy and the
    request fails with a retryable, structured error (HTTP 503 +
    Retry-After at the gateway).
    """


class AdapterBank:
    """Fixed-shape device bank + host LRU registry of named adapters."""

    def __init__(self, params, *, config: Optional[LoRAConfig] = None,
                 max_adapters: int = 8, dtype=jnp.float32):
        if max_adapters < 2:
            raise ValueError(
                f"max_adapters must be >= 2 (row 0 is the reserved identity "
                f"adapter; got {max_adapters})")
        self.config = config or LoRAConfig()
        self.max_adapters = int(max_adapters)
        self.rank = int(self.config.rank)
        self._dtype = dtype
        self._lock = threading.Lock()

        # Stacked zero bank: one [M, ...] leaf per target-module leaf.
        self._paths = target_paths(params, self.config)
        stacks: dict = {}
        self._shapes: dict = {}
        M, R = self.max_adapters, self.rank
        for dotted in self._paths:
            kernel = _get_path(params, dotted)["kernel"]
            d_in, d_out = int(kernel.shape[0]), int(kernel.shape[1])
            self._shapes[dotted] = (d_in, d_out)
            _set_path(stacks, dotted, {
                "a": jnp.zeros((M, d_in, R), dtype),
                "b": jnp.zeros((M, R, d_out), dtype),
                "scale": jnp.zeros((M,), dtype),
            })
        self.stacks = stacks

        # Host registry / residency. Row 0 is permanently the identity.
        self._registered: dict = {}            # name -> padded host adapter
        self._rows: dict = {}                  # resident name -> row index
        self._row_of: list = [None] * M        # row index -> name (None = free)
        self._lru: OrderedDict = OrderedDict()  # resident names, LRU -> MRU
        self._pins: dict = {}                  # name -> in-flight pin count
        self.loads = 0
        self.evictions = 0

        def write_row(stacks, row, host):
            return jax.tree_util.tree_map(
                lambda s, u: jax.lax.dynamic_update_slice(
                    s, u.astype(s.dtype)[None], (row,) + (0,) * u.ndim),
                stacks, host)

        self._write = jax.jit(write_row)
        self._write_row_fn = write_row
        self._placed_mesh = None
        # Compile the (only) row-write program up front by re-writing the
        # identity into row 0 — later loads reuse this executable.
        self.stacks = self._write(self.stacks, jnp.int32(0), self._identity())

    def place(self, shardings) -> None:
        """Shard the bank across a serving slice (mesh-sliced engines).

        ``shardings`` is a NamedSharding pytree matching :attr:`stacks`
        (from ``SliceExec.bank_shardings``: each target's LoRA factors laid
        out like its base kernel — column-parallel targets shard ``b`` on
        ``d_out``, row-parallel ``a`` on ``d_in``; the row axis never
        splits). The stacks move onto the slice and the row-write program
        is re-jitted with matching in/out shardings, so later
        loads/evictions keep writing ONE ``dynamic_update_slice`` per leaf
        straight into the sharded layout — residency stays recompile-free.

        Engine-construction time only, and once per bank: a bank placed on
        one slice cannot serve another (each ``from_mesh`` slice engine
        builds its own via ``make_adapters``).
        """
        from jax.sharding import NamedSharding, PartitionSpec

        leaves = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "spec"))
        mesh = leaves[0].mesh
        with self._lock:
            if self._placed_mesh is not None and self._placed_mesh != mesh:
                raise ValueError(
                    "AdapterBank is already placed on another mesh slice; "
                    "each mesh-sliced engine needs its OWN bank (pass a "
                    "make_adapters factory to ReplicaSet.from_mesh)")
            self._placed_mesh = mesh
            replicated = NamedSharding(mesh, PartitionSpec())
            self.stacks = jax.tree_util.tree_map(
                lambda s, sh: jax.device_put(s, sh), self.stacks, shardings)
            self._write = jax.jit(
                self._write_row_fn,
                in_shardings=(shardings, replicated, replicated),
                out_shardings=shardings)
            self.stacks = self._write(self.stacks, jnp.int32(0),
                                      self._identity())

    def commit(self, device) -> None:
        """Pin the bank to one device (single-chip engines): the stacks are
        committed there, so every later row write runs — and leaves its
        result — on the engine's own chip rather than on jax's default
        device. The identity re-write compiles the row-write program for
        the committed layout now, not at the first tenant load."""
        with self._lock:
            self.stacks = jax.device_put(self.stacks, device)
            self.stacks = self._write(self.stacks, jnp.int32(0),
                                      self._identity())

    # ------------------------------------------------------------------
    # host registry
    # ------------------------------------------------------------------

    def _identity(self):
        ident: dict = {}
        for dotted in self._paths:
            d_in, d_out = self._shapes[dotted]
            _set_path(ident, dotted, {
                "a": np.zeros((d_in, self.rank), np.float32),
                "b": np.zeros((self.rank, d_out), np.float32),
                "scale": np.zeros((), np.float32),
            })
        return ident

    @property
    def capacity(self) -> int:
        """Rows available to named adapters (row 0 is reserved)."""
        return self.max_adapters - 1

    def register(self, name: str, adapter, *, allow_update: bool = False) -> None:
        """Add a named adapter to the host registry (device load is lazy).

        The adapter may target any *subset* of the bank's modules and any
        rank <= the bank rank; missing modules become zero deltas and lower
        ranks are zero-padded, so heterogeneous tenants share one bank.
        """
        if not name or not isinstance(name, str):
            raise ValueError(f"adapter name must be a non-empty string (got {name!r})")
        r = adapter_rank(adapter)
        if r > self.rank:
            raise ValueError(
                f"adapter {name!r} has rank {r} > bank rank {self.rank}")
        padded = pad_adapter(adapter, self.rank)
        host = self._identity()
        for dotted in adapter_module_paths(padded):
            if dotted not in self._shapes:
                raise ValueError(
                    f"adapter {name!r} targets {dotted!r}, which is not a "
                    f"bank target (bank targets: {self._paths})")
            mod = _get_path(padded, dotted)
            d_in, d_out = self._shapes[dotted]
            got = (tuple(np.shape(mod["a"])), tuple(np.shape(mod["b"])))
            want = ((d_in, self.rank), (self.rank, d_out))
            if got != want:
                raise ValueError(
                    f"adapter {name!r} module {dotted!r} has shapes {got}, "
                    f"expected {want}")
            _set_path(host, dotted, {
                "a": np.asarray(jax.device_get(mod["a"]), np.float32),
                "b": np.asarray(jax.device_get(mod["b"]), np.float32),
                "scale": np.asarray(jax.device_get(mod["scale"]), np.float32),
            })
        with self._lock:
            if name in self._registered and not allow_update:
                raise ValueError(
                    f"adapter {name!r} is already registered "
                    "(pass allow_update=True to replace it)")
            if self._pins.get(name, 0) > 0:
                raise RuntimeError(
                    f"adapter {name!r} has in-flight requests; cannot replace")
            # Drop any stale residency so the next acquire reloads new bytes.
            row = self._rows.pop(name, None)
            if row is not None:
                self._row_of[row] = None
                self._lru.pop(name, None)
            self._registered[name] = host

    def unregister(self, name: str) -> None:
        with self._lock:
            if name not in self._registered:
                raise UnknownAdapterError(name)
            if self._pins.get(name, 0) > 0:
                raise RuntimeError(
                    f"adapter {name!r} has in-flight requests; cannot unregister")
            del self._registered[name]
            row = self._rows.pop(name, None)
            if row is not None:
                self._row_of[row] = None
                self._lru.pop(name, None)

    def names(self) -> list:
        with self._lock:
            return sorted(self._registered)

    def resident(self, name: str) -> bool:
        with self._lock:
            return name in self._rows

    def check_known(self, name: str) -> None:
        with self._lock:
            if name not in self._registered:
                known = sorted(self._registered)
                raise UnknownAdapterError(
                    f"unknown adapter {name!r} (registered: {known})")

    # ------------------------------------------------------------------
    # residency (engine thread)
    # ------------------------------------------------------------------

    def acquire(self, name: str):
        """Pin ``name`` into a bank row; load (and maybe evict) if absent.

        Returns ``(row, hit, evicted_name_or_None)``. Raises
        :class:`UnknownAdapterError` for unregistered names and
        :class:`AdapterBankFull` when every row is pinned by in-flight work.
        """
        with self._lock:
            if name not in self._registered:
                raise UnknownAdapterError(
                    f"unknown adapter {name!r} (registered: {sorted(self._registered)})")
            if name in self._rows:
                self._lru.move_to_end(name)
                self._pins[name] = self._pins.get(name, 0) + 1
                return self._rows[name], True, None

            evicted = None
            row = next(
                (i for i in range(1, self.max_adapters) if self._row_of[i] is None),
                None)
            if row is None:
                for cand in self._lru:  # LRU -> MRU
                    if self._pins.get(cand, 0) == 0:
                        evicted = cand
                        break
                if evicted is None:
                    raise AdapterBankFull(
                        f"all {self.capacity} adapter rows are pinned by "
                        f"in-flight requests; retry adapter {name!r} later")
                row = self._rows.pop(evicted)
                self._lru.pop(evicted)
                self._row_of[row] = None
                self.evictions += 1

            # Row write runs on the engine thread only; reassigning
            # self.stacks functionally keeps compiled callers coherent.
            self.stacks = self._write(
                self.stacks, jnp.int32(row), self._registered[name])
            self._rows[name] = row
            self._row_of[row] = name
            self._lru[name] = None
            self._pins[name] = self._pins.get(name, 0) + 1
            self.loads += 1
            return row, False, evicted

    def release(self, name: str) -> None:
        with self._lock:
            n = self._pins.get(name, 0)
            if n <= 1:
                self._pins.pop(name, None)
            else:
                self._pins[name] = n - 1

    def counters(self) -> dict:
        with self._lock:
            return {
                "registered": len(self._registered),
                "resident": len(self._rows),
                "capacity": self.capacity,
                "loads": self.loads,
                "evictions": self.evictions,
            }
