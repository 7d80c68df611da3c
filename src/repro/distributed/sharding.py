"""Sharding utilities: logical-axis constraints that degrade gracefully.

Models are written against *logical* axes ("batch", "seq", "tp", "exp", …).
``mesh_context`` records which physical mesh axes exist; ``shard`` applies a
``with_sharding_constraint`` only when every referenced physical axis is
present, so the same model code runs

  * unsharded on one CPU device (smoke tests),
  * GSPMD-sharded under the production meshes (dry-run / real pods).

Physical mapping (DESIGN.md Section 4):

  batch  -> ("pod", "data")     DP over pods x data axis
  tp     -> "model"             tensor parallel / expert parallel / seq shard
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


LOGICAL_TO_PHYSICAL: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "tp": ("model",),
    "seq": ("model",),   # sequence sharding rides the model axis
    "exp": ("model",),   # expert parallelism rides the model axis
    None: (),
}


def current_mesh_axes() -> Tuple[str, ...]:
    return getattr(_state, "axes", ())


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextmanager
def mesh_context(mesh: Optional[Mesh]):
    """Enter a mesh: activates both jax's mesh context and logical sharding."""
    if mesh is None:
        yield
        return
    prev_axes = getattr(_state, "axes", ())
    prev_mesh = getattr(_state, "mesh", None)
    _state.axes = tuple(mesh.axis_names)
    _state.mesh = mesh
    try:
        with mesh:
            yield
    finally:
        _state.axes = prev_axes
        _state.mesh = prev_mesh


def _resolve(logical: Optional[str]) -> Optional[Tuple[str, ...]]:
    """Logical name -> tuple of available physical axes (None if none)."""
    axes = current_mesh_axes()
    if logical is None:
        return None
    phys = tuple(a for a in LOGICAL_TO_PHYSICAL.get(logical, (logical,))
                 if a in axes)
    return phys if phys else None


def spec(*logical: Optional[str]) -> P:
    parts = []
    for l in logical:
        r = _resolve(l)
        parts.append(r if r else None)
    return P(*parts)


def shard(x, *logical: Optional[str]):
    """Constrain ``x`` to the logical spec; no-op outside a mesh."""
    if not current_mesh_axes():
        return x
    return jax.lax.with_sharding_constraint(x, spec(*logical))


def logical_shard(x, spec_: P):
    if not current_mesh_axes():
        return x
    return jax.lax.with_sharding_constraint(x, spec_)


def axis_size(logical: str) -> int:
    """Product of the physical axis sizes behind a logical axis (1 if absent)."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    n = 1
    for a in _resolve(logical) or ():
        n *= mesh.shape[a]
    return n


def named(mesh: Mesh, *parts) -> NamedSharding:
    return NamedSharding(mesh, P(*parts))


# ---------------------------------------------------------------------------
# host-to-host byte transport (Recorder's point-to-point reduce carrier)
# ---------------------------------------------------------------------------
#
# jax has no independent pairwise send: its point-to-point primitive is
# ``lax.ppermute``, a COLLECTIVE permutation every process participates in.
# ``PpermuteByteTransport.exchange`` therefore moves one log-round's worth
# of pair payloads together -- ``comm.reduce_tree_via_exchange`` calls it
# once per round with the round's (src, dst) list -- and only the round's
# senders contribute non-empty arrays.  Payloads are opaque bytes
# (serialized RankStates), packed into fixed-size length-prefixed uint8
# device arrays so every process contributes an identically-shaped operand
# (the SPMD requirement).

#: presence byte + 4-byte little-endian payload length
_LEN_HEADER = 5

#: mesh axis the host transport permutes over (one device per process)
HOST_AXIS = "hosts"


def pack_bytes_array(payload: Optional[bytes], pad_to: int) -> np.ndarray:
    """A byte payload as a fixed-size uint8 array: 1 presence byte, 4-byte
    little-endian length, payload, zero padding.  ``None`` (rank sends
    nothing this round) is distinct from ``b""`` -- the presence byte
    round-trips it."""
    n = 0 if payload is None else len(payload)
    if pad_to < n + _LEN_HEADER:
        raise ValueError(
            f"pad_to={pad_to} cannot hold a {n}-byte payload plus the "
            f"{_LEN_HEADER}-byte header")
    arr = np.zeros(pad_to, dtype=np.uint8)
    if payload is not None:
        arr[0] = 1
        arr[1:5] = np.frombuffer(n.to_bytes(4, "little"), dtype=np.uint8)
        if n:
            arr[_LEN_HEADER : _LEN_HEADER + n] = np.frombuffer(
                payload, dtype=np.uint8)
    return arr


def unpack_bytes_array(arr) -> Optional[bytes]:
    """Inverse of :func:`pack_bytes_array` (padding ignored)."""
    a = np.asarray(arr, dtype=np.uint8).reshape(-1)
    if a.size < _LEN_HEADER or a[0] == 0:
        return None
    n = int.from_bytes(a[1:5].tobytes(), "little")
    return a[_LEN_HEADER : _LEN_HEADER + n].tobytes()


class PpermuteByteTransport:
    """Collective point-to-point byte mover between jax host processes.

    ``exchange(payload, perm)`` must be called by EVERY process with the
    same ``perm`` (a list of ``(src, dst)`` process pairs); it returns the
    payload addressed to this process, or None.  Wire path: allgather the
    payload lengths to agree on a common array size, pack to uint8, lay
    the per-host arrays out over a 1-D ``hosts`` mesh (one device per
    process) and move them with a single shard_map'd ``lax.ppermute``.

    Requires a multi-process jax runtime; with one process every schedule
    is empty, so ``exchange`` is never reached (``comm.JaxComm`` guards).
    """

    def __init__(self, mesh: Optional[Mesh] = None):
        self._mesh = mesh

    def _host_mesh(self) -> Mesh:
        if self._mesh is None:
            devs = [jax.local_devices(process_index=p)[0]
                    for p in range(jax.process_count())]
            self._mesh = Mesh(np.asarray(devs), (HOST_AXIS,))
        return self._mesh

    def exchange(self, payload: Optional[bytes],
                 perm: List[Tuple[int, int]]) -> Optional[bytes]:
        if not perm:
            return None
        from jax.experimental import multihost_utils

        n = 0 if payload is None else len(payload)
        cap = int(multihost_utils.process_allgather(
            np.asarray([n], np.int64)).max()) + _LEN_HEADER
        # a power of two, so a finalize compiles the exchange for a few
        # array sizes rather than one per payload length
        cap = 1 << max(6, (cap - 1).bit_length())
        local = pack_bytes_array(payload, cap)[None, :]
        mesh = self._host_mesh()
        spec_ = P(HOST_AXIS, None)
        global_arr = multihost_utils.host_local_array_to_global_array(
            local, mesh, spec_)
        shifted = jax.shard_map(
            lambda x: jax.lax.ppermute(x, HOST_AXIS, perm),
            mesh=mesh, in_specs=spec_, out_specs=spec_)(global_arr)
        back = multihost_utils.global_array_to_host_local_array(
            shifted, mesh, spec_)
        return unpack_bytes_array(np.asarray(back)[0])


def global_any(flag: bool) -> bool:
    """Cross-process boolean OR (the flush-cadence vote): allgather one
    uint8 per process and reduce locally.  Identity with one process."""
    if jax.process_count() == 1:
        return bool(flag)
    from jax.experimental import multihost_utils

    votes = multihost_utils.process_allgather(
        np.asarray([1 if flag else 0], np.uint8))
    return bool(np.asarray(votes).any())


# ---------------------------------------------------------------------------
# parameter sharding rules
# ---------------------------------------------------------------------------

#: leaf-name suffix -> PartitionSpec factory. Parameters are named
#: hierarchically ("layers/attn/wq", …); the *last* matching rule wins.
#: Conventions: weight matrices (in, out); stacked-layer params have a
#: leading layer dim handled by ``stacked=True``.

def param_sharding_rules(name: str, shape: Tuple[int, ...],
                         tp: str = "model") -> P:
    """Sharding spec for one parameter by naming convention.

    Layout rules (MaxText-style):
      embeddings       (vocab, d)        -> (tp, None)   vocab-sharded
      attn in-proj     (d, heads*hd)     -> (None, tp)   head-sharded
      attn out-proj    (heads*hd, d)     -> (tp, None)
      mlp in/gate      (d, ff)           -> (None, tp)
      mlp out          (ff, d)           -> (tp, None)
      experts          (E, d, ff)        -> (tp, None, None)  expert-sharded
      biases/norms/small vectors         -> replicated
    Stacked-layer params carry a leading layer axis (never sharded).
    """
    parts: list = []
    lead = 0
    if name.startswith("layers/") or name.startswith("enc_layers/") or \
            name.startswith("dec_layers/"):
        lead = 1  # scan-stacked leading layer dim
    base = [None] * (len(shape) - lead)
    ndim = len(base)

    def out(spec_parts):
        return P(*([None] * lead + list(spec_parts)))

    leaf = name.rsplit("/", 1)[-1]
    if ndim <= 1:
        return out(base)  # norms, biases, scalars: replicated
    # expert-stacked weights: (E, d_in, d_out) -> shard experts over tp
    if leaf in ("w_gate_e", "w_up_e", "w_down_e") and ndim == 3:
        return out([tp, None, None])
    if leaf in ("embed", "lm_head", "dec_embed"):
        return out([tp, None])
    if leaf in ("wq", "wk", "wv", "wkv", "w_gate", "w_up", "in_proj",
                "w_dkv", "w_kr", "w_uk", "w_uv", "w_q"):
        return out([None] * (ndim - 1) + [tp])
    if leaf in ("wo", "w_down", "out_proj"):
        return out([tp] + [None] * (ndim - 1))
    if leaf == "router":
        return out([None] * ndim)
    return out(base)


def tree_param_specs(params, tp: str = "model"):
    """Map a {name: leaf} flat dict (or pytree with '/'-joined key paths)
    to PartitionSpecs using ``param_sharding_rules``."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def path_name(path) -> str:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
        return "/".join(parts)

    specs = {path_name(path): param_sharding_rules(path_name(path),
                                                   leaf.shape, tp)
             for path, leaf in flat}
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(
        treedef, [specs[path_name(p)] for p, _ in flat])
