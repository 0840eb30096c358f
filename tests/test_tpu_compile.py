"""The BAD data plane's Pallas kernels compile for a TPU v5e.

Each case compiles a kernel wrapper for one chip of a described (not
attached) ``v5e:2x2`` topology, in the forms the engine calls it — the
ingest-time ``predicate_filter``, the fused executor's vmapped
``predicate_filter_rows`` and channel-stacked ``spatial_match``, and the
compacted join's ``join_compact`` — and asserts the compiled program holds
the Mosaic kernel (``tpu_custom_call``) rather than interpreted XLA ops.
The broker's ring-aware delivery compiles at a deployment's spatial shape
with no binary-search loop in its send stage. Nothing runs: this catches
what the TPU compiler refuses (unaligned blocks, too much VMEM) without a
chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. Keep all such compiles in this one file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import broker
from repro.core import records as R
from repro.core.plans import ChannelResult
from repro.core.channel import tweets_about_crime, tweets_about_drugs
from repro.core.predicates import compile_conditions
from repro.kernels.join_compact import ops as jc_ops
from repro.kernels.predicate_filter import ops as pf_ops
from repro.kernels.spatial_match import ops as sm_ops

F = R.ENRICHED_TWEET_SCHEMA.num_fields


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_tpu(one_chip, monkeypatch):
    """Compile ``fn`` at the given shapes for the described chip, with the
    ops wrappers steered to their TPU branch and the persistent compilation
    cache off (a TPU executable compiled here cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    for ops in (pf_ops, sm_ops, jc_ops):
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
    was = jax.config.values["jax_enable_compilation_cache"]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _conds(num_channels):
    specs = [tweets_about_drugs()] + [tweets_about_crime(k)
                                      for k in (3, 4, 5)]
    return compile_conditions([list(s.fixed_preds)
                               for s in specs[:num_channels]])


@pytest.mark.parametrize("num_channels", [1, 4])
def test_predicate_filter_compiles(compile_for_tpu, num_channels):
    conds = _conds(num_channels)
    text = compile_for_tpu(lambda f: pf_ops.predicate_filter(f, conds),
                           ((32768, F), jnp.int32))
    assert "tpu_custom_call" in text


def test_predicate_filter_rows_compiles(compile_for_tpu):
    conds = _conds(4)
    text = compile_for_tpu(lambda f: pf_ops.predicate_filter_rows(f, conds),
                           ((4, 32768, F), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("channels", [None, 4])
def test_spatial_match_compiles(compile_for_tpu, channels):
    lead = () if channels is None else (channels,)
    radius = ((), jnp.float32) if channels is None else (lead, jnp.float32)
    text = compile_for_tpu(sm_ops.spatial_match,
                           (lead + (4096, 2), jnp.float32),
                           (lead + (65536, 2), jnp.float32), radius)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("aggregated", [True, False])
def test_join_compact_compiles(compile_for_tpu, aggregated):
    s, max_t = 8192, 64
    text = compile_for_tpu(
        lambda *a: jc_ops.join_pairs(*a, num_brokers=4,
                                     aggregated=aggregated),
        ((s, max_t), jnp.int32), ((s,), jnp.int32), ((s, max_t), jnp.int32),
        ((s, max_t), jnp.int32), ((s,), jnp.int32), ((s,), jnp.int32))
    assert "tpu_custom_call" in text


def test_send_stage_compiles_without_search(compile_for_tpu):
    """Ring-aware delivery of one spatial channel at the §5.1 cell's shape:
    128 candidate rows x 16,384 located users (P = 2,097,152 pairs) into
    8,388,608 notify slots. No ``while`` carries ``bad.send``; the ring
    tail and spill lookups keep their search under ``bad.ring``."""
    rows, users, max_notify, window = 128, 16384, 8388608, 4096

    def deliver(pair_rows, pair_targets, pair_valid, brokers, ring_cw,
                ring_c):
        z = jnp.zeros((1,), jnp.int32)
        res = ChannelResult(pair_rows, pair_targets, pair_valid,
                            pair_rows[:, :, 0], pair_valid[:, :, 0], z, z, z,
                            jnp.zeros((1, 4), jnp.int32),
                            jnp.zeros((1, 4), jnp.int32))
        ring = broker.RetryRing(ring_cw, ring_cw, ring_cw, ring_c, ring_cw,
                                ring_c)
        return broker.deliver_all(res, jnp.zeros((1, 0), jnp.int32), 8,
                                  16384, max_notify, 8192,
                                  target_brokers=brokers, num_brokers=4,
                                  ring=ring, epochs=ring_c)

    grid = (1, rows, users)
    text = compile_for_tpu(deliver, (grid, jnp.int32), (grid, jnp.int32),
                           (grid, jnp.bool_), ((1, users), jnp.int32),
                           ((1, window), jnp.int32), ((1,), jnp.int32))
    loops = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines() if re.search(r"\bwhile\(", line)]
    assert not [name for name in loops if "/bad.send/" in name], loops
    assert [name for name in loops if "/bad.ring/" in name], loops
