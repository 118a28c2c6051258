"""Level-pool storage seam: host numpy slabs vs device-resident slabs.

``_LevelPool`` is the single owner of closed-node matrices for one tree
level (higgslint R2 enforces that every other module goes through its
``gather()``/``gather_block()`` API instead of poking slab arrays).  The
pool delegates raw array storage to one of two interchangeable backends:

* ``HostPoolStorage`` — numpy slabs with true in-place appends, the CPU
  default and the bit-reference for everything else.
* ``DevicePoolStorage`` — persistent jax device slabs.  Appends, slides
  and gathers run on device; host code sees the slabs only through
  explicit snapshot barriers (``host_view``/``host_block``), which is
  what lets the fused ingest pipeline update pool state with donated
  buffers instead of re-uploading it every batch.

Both backends are bit-identical: they initialize capacity from the same
``empty_node_arrays`` pattern and store exactly the bytes they are
handed.  Node ids are **global** (stable across the stream's lifetime)
while the slabs hold only the retained window: ``base`` counts nodes the
segment-store lifecycle has dropped from the front, so global id ``u``
lives at physical slot ``u - base``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cmatrix
from repro.core.cmatrix import EMPTY, NodeState

STORAGE_KINDS = ("host", "device")


def _empty_device_slabs(n: int, d: int, b: int) -> dict:
    """Device twin of ``cmatrix.empty_node_arrays`` — same EMPTY/zero
    fill pattern so unused capacity matches the host backend bit for
    bit."""
    shape = (n, d, d, b)
    return {name: jnp.full(shape, EMPTY, jnp.uint32)
            if name in ("fp_s", "fp_d")
            else jnp.zeros(shape, jnp.float32 if name == "w" else jnp.uint32)
            for name in NodeState._fields}


@jax.jit
def _slide_slabs(slabs: dict, n, k) -> dict:
    """Rows ``[0, n - k)`` of every slab take rows ``[k, n)``; rows from
    ``n - k`` on keep their contents, as ``HostPoolStorage.slide``
    leaves them.  ``n`` and ``k`` are traced int32 scalars, so one
    program serves every node count of a pool's capacity.  Not donated:
    the input slabs stay valid."""
    cap = next(iter(slabs.values())).shape[0]
    rows = jnp.arange(cap, dtype=jnp.int32)
    src = jnp.where(rows < n - k, rows + k, rows)
    return {name: jnp.take(slab, src, axis=0, mode="clip")
            for name, slab in slabs.items()}


class HostPoolStorage:
    """Numpy slab storage (in-place mutation, zero-cost host view)."""

    kind = "host"

    def __init__(self, d: int, b: int):
        self.d, self.b = d, b
        self.slabs: Optional[dict] = None
        self.cap = 0

    def grow(self, n: int, new_cap: int) -> None:
        new = cmatrix.empty_node_arrays(new_cap, self.d, self.b)
        if self.slabs is not None:
            for name in NodeState._fields:
                new[name][:n] = self.slabs[name][:n]
        self.slabs = new
        self.cap = new_cap

    def clear(self) -> None:
        self.slabs = None
        self.cap = 0

    def write_row(self, i: int, node: NodeState) -> None:
        for name in NodeState._fields:
            self.slabs[name][i] = np.asarray(getattr(node, name))

    def write_block(self, i0: int, arrs: dict, count: int) -> None:
        for name in NodeState._fields:
            self.slabs[name][i0:i0 + count] = np.asarray(arrs[name][:count])

    def slide(self, n: int, k: int) -> None:
        """Move rows [k, n) to the front (retention drop_prefix)."""
        for name in NodeState._fields:
            arr = self.slabs[name]
            arr[: n - k] = arr[k:n].copy()

    def host_view(self) -> Optional[dict]:
        return self.slabs

    def host_block(self, i0: int, count: int) -> dict:
        return {name: self.slabs[name][i0:i0 + count]
                for name in NodeState._fields}

    def device_slabs(self) -> dict:
        return {name: jnp.asarray(self.slabs[name])
                for name in NodeState._fields}

    def gather_rows(self, idx: np.ndarray) -> NodeState:
        return NodeState(*(jnp.asarray(self.slabs[name][idx])
                           for name in NodeState._fields))


class DevicePoolStorage:
    """Persistent jax device slabs (functional updates, donated where the
    fused pipeline drives them).  Eager ``.at[].set`` appends copy the
    slab on CPU; the pallas fused-drain path avoids that by scattering
    inside a jit with donated slab operands (`kernels/pipeline.py`)."""

    kind = "device"

    def __init__(self, d: int, b: int):
        self.d, self.b = d, b
        self.slabs: Optional[dict] = None
        self.cap = 0

    def grow(self, n: int, new_cap: int) -> None:
        new = _empty_device_slabs(new_cap, self.d, self.b)
        if self.slabs is not None and n:
            new = {name: new[name].at[:n].set(self.slabs[name][:n])
                   for name in NodeState._fields}
        self.slabs = new
        self.cap = new_cap

    def clear(self) -> None:
        self.slabs = None
        self.cap = 0

    def write_row(self, i: int, node: NodeState) -> None:
        self.slabs = {name: self.slabs[name].at[i].set(
            jnp.asarray(getattr(node, name)))
            for name in NodeState._fields}

    def write_block(self, i0: int, arrs: dict, count: int) -> None:
        self.slabs = {name: self.slabs[name].at[i0:i0 + count].set(
            jnp.asarray(arrs[name][:count]))
            for name in NodeState._fields}

    def slide(self, n: int, k: int) -> None:
        self.slabs = _slide_slabs(self.slabs, np.int32(n), np.int32(k))

    def host_view(self) -> Optional[dict]:
        if self.slabs is None:
            return None
        return {name: np.asarray(self.slabs[name])
                for name in NodeState._fields}

    def host_block(self, i0: int, count: int) -> dict:
        return {name: np.asarray(self.slabs[name][i0:i0 + count])
                for name in NodeState._fields}

    def device_slabs(self) -> dict:
        return self.slabs

    def adopt(self, slabs: dict) -> None:
        """Replace the slabs wholesale (fused-pipeline donation return)."""
        self.slabs = slabs

    def gather_rows(self, idx: np.ndarray) -> NodeState:
        di = jnp.asarray(np.asarray(idx, np.int32))
        return NodeState(*(jnp.take(self.slabs[name], di, axis=0)
                           for name in NodeState._fields))


_STORAGES = {"host": HostPoolStorage, "device": DevicePoolStorage}


class _LevelPool:
    """Closed-node matrices for one tree level, behind the storage seam.

    Under ``storage="host"`` behavior is bit-identical to the original
    numpy pool (query gathers upload only the probed subset).  Under
    ``storage="device"`` the slabs are persistent device arrays: appends
    and retention slides stay on device, gathers never touch the host,
    and host reads (snapshots, sanitize, aggregation child blocks) are
    explicit fetch barriers.
    """

    def __init__(self, d: int, b: int, storage: str = "host",
                 stats=None):
        if storage not in _STORAGES:
            raise ValueError(f"unknown pool storage {storage!r}")
        self.d, self.b = d, b
        # the owning summary's IngestStats (grows and slides), or None
        self.stats = stats
        self.n = 0
        self.cap = 0
        self.base = 0
        self._st = _STORAGES[storage](d, b)
        # mutation epoch: bumped on every write so the lazily-built
        # mirrors below (host snapshot of device slabs, device mirror of
        # host slabs) invalidate without eager copies
        self._version = 0
        self._host_mirror: tuple[int, Optional[dict]] = (-1, None)
        self._device_mirror: tuple[int, Optional[NodeState]] = (-1, None)

    # -- storage introspection ------------------------------------------

    @property
    def storage_kind(self) -> str:
        return self._st.kind

    @property
    def total(self) -> int:
        """Global node count ever appended (retained + dropped)."""
        return self.base + self.n

    @property
    def arrs(self) -> Optional[dict]:
        """Host-materialized slab fields (read-only by convention).

        For host storage this is the live numpy storage (free); for
        device storage it is a cached snapshot fetched at most once per
        mutation epoch — a d2h barrier, which is exactly where
        ``state_dict``/sanitize/inspection are meant to pay it.
        """
        if self._st.kind == "host":
            return self._st.host_view()
        ver, cached = self._host_mirror
        if ver != self._version or cached is None:
            cached = self._st.host_view()
            self._host_mirror = (self._version, cached)
        return cached

    def _dirty(self) -> None:
        self._version += 1

    # -- lifecycle -------------------------------------------------------

    def drop_prefix(self, k: int) -> None:
        """Reclaim the ``k`` oldest retained slots (segment eviction /
        coarsening): the retained suffix slides to the front in place,
        capacity is kept for reuse by future appends."""
        if k <= 0:
            return
        if k > self.n:
            raise ValueError(f"cannot drop {k} of {self.n} nodes")
        self._st.slide(self.n, k)
        self.n -= k
        self.base += k
        self._dirty()
        if self.stats is not None:
            self.stats.slides += 1
            if self._st.kind == "device":
                self.stats.launches += 1

    def _grow(self, new_cap: int) -> None:
        self._st.grow(self.n, new_cap)
        self.cap = new_cap
        self._dirty()
        if self.stats is not None:
            # five 4-byte fields per matrix cell
            self.stats.pool_grows += 1
            self.stats.pool_grow_bytes += (new_cap * self.d * self.d
                                           * self.b * 4
                                           * len(NodeState._fields))

    def reserve(self, need: int) -> None:
        """Grow capacity (power-of-two schedule) to hold ``need`` nodes
        without writing any — the fused ingest pipeline sizes slabs
        before launching so the kernel scatters into final storage."""
        if need <= self.cap:
            return
        cap = max(4, self.cap)
        while cap < need:
            cap *= 2
        self._grow(cap)

    def load(self, arrs: dict, n: int, cap: int | None = None,
             base: int = 0) -> None:
        """Overwrite this pool with ``n`` snapshot nodes, re-growing to
        the saved capacity so post-restore allocation behavior matches
        the uninterrupted run exactly."""
        self._st.clear()
        self.n = 0
        self.cap = 0
        self.base = int(base)
        self._dirty()
        cap = max(cap if cap is not None else n, n)
        if cap == 0:
            return
        self._grow(cap)
        self._st.write_block(0, arrs, n)
        self.n = n
        self._dirty()

    # -- appends ---------------------------------------------------------

    def append(self, node: NodeState) -> int:
        if self.n == self.cap:
            self._grow(max(4, self.cap * 2))
        self._st.write_row(self.n, node)
        idx = self.n
        self.n += 1
        self._dirty()
        return idx

    def append_batch(self, arrs: dict, count: int) -> int:
        """Append ``count`` nodes from stacked field arrays in one block
        copy; returns the base node id."""
        self.reserve(self.n + count)
        self._st.write_block(self.n, arrs, count)
        base = self.n
        self.n += count
        self._dirty()
        return base

    def adopt_slabs(self, slabs: dict, count: int) -> int:
        """Adopt fused-pipeline output: the donated device slabs already
        contain ``count`` freshly scattered nodes past ``self.n``.
        Device storage only; returns the base node id of the batch."""
        if self._st.kind != "device":
            raise ValueError("adopt_slabs requires device storage")
        self._st.adopt(slabs)
        base = self.n
        self.n += count
        self._dirty()
        return base

    # -- reads -----------------------------------------------------------

    def gather(self, ids: np.ndarray, pad_to: int):
        """(NodeState stacked to pad_to, mask) for a list of **global**
        node ids; the window translation to physical slots happens here
        so every caller keeps speaking stable ids."""
        m = len(ids)
        idx = np.zeros((pad_to,), np.int64)
        idx[:m] = np.asarray(ids, np.int64) - self.base
        mask = np.zeros((pad_to,), bool)
        mask[:m] = True
        nodes = self._st.gather_rows(idx)
        return nodes, jnp.asarray(mask)

    def gather_block(self, u0: int, count: int) -> dict:
        """Host-materialized contiguous block of ``count`` nodes from
        **global** id ``u0`` (the aggregation child gather).  Under
        device storage this fetches exactly the child block — a bounded
        d2h barrier — never the whole slab."""
        i0 = u0 - self.base
        if i0 < 0 or i0 + count > self.n:
            raise ValueError(
                f"block [{u0}, {u0 + count}) outside retained window "
                f"[{self.base}, {self.base + self.n})")
        return self._st.host_block(i0, count)

    def gather_ids(self, ids: np.ndarray, pad_to: int):
        """Physical slot indices + mask for a probe over global ids —
        the host-side half of the fused gather+probe launch (the row
        take itself happens inside the jit against ``device_view``)."""
        m = len(ids)
        idx = np.zeros((pad_to,), np.int32)
        idx[:m] = (np.asarray(ids, np.int64) - self.base).astype(np.int32)
        mask = np.zeros((pad_to,), bool)
        mask[:m] = True
        return idx, mask

    def device_view(self) -> NodeState:
        """Full-capacity slabs as device arrays for fused probes.

        Device storage returns its live slabs (free); host storage keeps
        a device mirror uploaded at most once per mutation epoch, so a
        burst of queries between drains pays one h2d transfer, not one
        per launch.
        """
        if self._st.kind == "device":
            return NodeState(**self._st.device_slabs())
        ver, cached = self._device_mirror
        if ver != self._version or cached is None:
            cached = NodeState(**self._st.device_slabs())
            self._device_mirror = (self._version, cached)
        return cached

    def device_slabs(self) -> dict:
        """Raw device slab dict (fused ingest input; device storage)."""
        return self._st.device_slabs()

    def pin_view(self) -> "_LevelPool":
        """Zero-copy read-only clone sharing the live host slabs.

        Valid only for host storage with a dormant segment lifecycle:
        the writer then mutates shared slabs exclusively by appending
        past ``n`` (invisible to the pin, which reads through its own
        frozen ``n``) or by copy-on-grow (which rebinds the writer's
        slab dict, leaving the pin on the old arrays).  Retention
        slides mutate retained rows in place and would corrupt the
        pin — :meth:`HiggsSketch._pin_replica` routes those
        configurations through the deep snapshot path instead.
        """
        if self._st.kind != "host":
            raise ValueError("pin_view requires host pool storage")
        clone = _LevelPool(self.d, self.b, storage="host")
        clone._st.slabs = self._st.slabs
        clone._st.cap = self.cap
        clone.cap = self.cap
        clone.n = self.n
        clone.base = self.base
        return clone
