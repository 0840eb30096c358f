"""shard_map collectives: sequence-parallel flash-decode attention and the
sharded BAD engine's cross-shard notification shuffle.

The KV cache for serving is sharded over the `model` axis on the *sequence*
dimension (works for every GQA geometry — head counts never need to divide
the axis). Each model shard computes flash partials (acc, m, l) over its local
KV slice; the merge is an exact log-sum-exp combine using one pmax + one psum
of (B, H, D)-sized tensors — O(B·H·D) bytes instead of re-reading the cache.

This is the TPU analogue of FlashDecoding split-KV, expressed as a collective
schedule instead of a grid.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.distributed.partition import Rules, sanitize_spec
from repro.kernels.flash_decode import ref as fd_ref


def sp_decode_attention(rules: Rules, q: jnp.ndarray, k: jnp.ndarray,
                        v: jnp.ndarray, kv_len: jnp.ndarray,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """q (B, H, D); k/v (B, KH, S, D) seq-sharded; kv_len (B,) -> (B, H, D)."""
    mesh = rules.mesh
    m_axis = rules.model_axis
    if m_axis is None:
        return fd_ref.decode_attention(q, k, v, kv_len, scale)
    n_shards = mesh.shape[m_axis]
    b, h, d = q.shape
    s = k.shape[2]
    b_spec = rules.batch_axes if rules.batch_axes else None
    bq = sanitize_spec(P(b_spec, None, None), q.shape, mesh)
    bkv = sanitize_spec(P(b_spec, None, m_axis, None), k.shape, mesh)
    blen = sanitize_spec(P(b_spec), kv_len.shape, mesh)
    shard_size = s // n_shards

    def local(qs, ks, vs, lens):
        # Local slice covers absolute kv positions [idx*shard, (idx+1)*shard).
        idx = jax.lax.axis_index(m_axis)
        local_len = jnp.clip(lens - idx * shard_size, 0, shard_size)
        acc, m, l = fd_ref.decode_attention_partial(qs, ks, vs, local_len, scale)
        m_g = jax.lax.pmax(m, m_axis)
        m_safe = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
        c = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        acc = jax.lax.psum(acc * c[..., None], m_axis)
        l = jax.lax.psum(l * c, m_axis)
        return fd_ref.normalize(acc, l, qs.dtype)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(bq, bkv, bkv, blen),
                       out_specs=bq)
    return fn(q, k, v, kv_len)


# ---------------------------------------------------------------------------
# cross-shard notification routing (the sharded BAD engine, core/sharded.py)
#
# Each shard's fused delivery emits a notify buffer of end-subscriber sIDs;
# the subscription lives on the shard its sID hashes to, but its BROKER
# endpoint lives on ``partition.broker_owner(bid) % S`` — a different shard
# for most (sid, broker) combinations. ``shuffle_notify`` regroups every
# shard's delivered sIDs by owner shard in one collective over the ("shard",)
# mesh axis, so outbound broker traffic leaves from the shard that hosts the
# endpoint. Deterministic order (source-shard-major, then slot order) makes
# the result exactly comparable against the pure-host reference.
# ---------------------------------------------------------------------------


def notify_mesh(num_shards: int) -> Mesh:
    """A ("shard",)-axis mesh over the first ``num_shards`` devices. Raises
    when the runtime has fewer devices than shards: routing never falls
    back to the host reference ``shuffle_notify_ref`` (which stays the
    tests' oracle). On CPU the devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, set before the
    process starts."""
    devices = jax.devices()
    if len(devices) < num_shards:
        raise RuntimeError(
            f"notify shuffle over {num_shards} shards needs {num_shards} "
            f"devices; the runtime has {len(devices)}")
    return Mesh(np.array(devices[:num_shards]), ("shard",))


def shuffle_notify_ref(sids: np.ndarray, owners: np.ndarray,
                       num_shards: int) -> np.ndarray:
    """Host reference for ``shuffle_notify``: sids/owners are (S, cap) with
    -1 padding; returns (num_shards, S*cap) where row o holds the sIDs owned
    by shard o in source-shard-major order, -1 padded."""
    sids = np.asarray(sids)
    owners = np.asarray(owners)
    s, cap = sids.shape
    out = np.full((num_shards, s * cap), -1, np.int32)
    for o in range(num_shards):
        picked = sids[(owners == o) & (sids >= 0)]
        out[o, :picked.size] = picked
    return out


def shuffle_notify(mesh: Mesh, sids: jnp.ndarray,
                   owners: jnp.ndarray) -> jnp.ndarray:
    """Collective all-gather shuffle: route delivered sIDs to their owner
    shards. ``sids``/``owners`` are (S, cap) int32, -1 padded, one row per
    source shard; the result is (S, S*cap), row o = shard o's inbound sIDs
    (source-shard-major, slot order, -1 padded) — bit-identical to
    ``shuffle_notify_ref``. Output shapes are static (S*cap), so steady
    ticks replay the cached trace."""
    axis = mesh.axis_names[0]
    s, cap = sids.shape
    out_cap = s * cap

    def local(sid_block, owner_block):
        # (1, cap) local block -> full (S, cap) view, then keep what's mine
        sid_all = jax.lax.all_gather(sid_block, axis, tiled=True).ravel()
        owner_all = jax.lax.all_gather(owner_block, axis, tiled=True).ravel()
        me = jax.lax.axis_index(axis)
        mine = (owner_all == me) & (sid_all >= 0)
        pos = jnp.cumsum(mine.astype(jnp.int32)) - 1
        out = jnp.full((out_cap + 1,), -1, jnp.int32)
        out = out.at[jnp.where(mine, pos, out_cap)].set(
            jnp.where(mine, sid_all, -1), mode="drop")
        return out[:out_cap][None, :]

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(axis, None), P(axis, None)),
                       out_specs=P(axis, None))
    return fn(jnp.asarray(sids, jnp.int32), jnp.asarray(owners, jnp.int32))
