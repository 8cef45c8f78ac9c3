"""The hop step compiles for a TPU v5e chip, at the paper's widths.

Each case compiles ``make_stream_hop`` at ``tftnn_config()`` widths, batch 8,
for one chip of a described ``v5e:2x2`` topology — no chip needed, the TPU
compiler is installed with JAX. Pallas kernels are lowered natively (Mosaic
``tpu_custom_call``), so a kernel the chip's compiler refuses (a misaligned
block, too much VMEM) fails here rather than on the chip; the xla step must
hold none. A compile is not a run: nothing here says the results are right
or how fast they come.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.models import tftnn as tft
from repro.serve.streaming_se import init_stream, make_stream_hop

BATCH = 8

CASES = {
    "xla": dict(backend="xla"),
    "pallas": dict(backend="pallas"),
    "pallas-fused-ring": dict(backend="pallas", max_hops_per_step=2, from_ring=4),
    "pallas-pruned-block": dict(backend="pallas", prune_keep=0.5,
                                prune_granularity="block"),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the TPU library logs to /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model():
    cfg = tft.tftnn_config()
    return cfg, tft.init_tft(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("case", sorted(CASES))
def test_hop_step_compiles_for_v5e(case, one_chip, model, monkeypatch,
                                   no_persistent_cache):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")  # native Mosaic lowering
    cfg, params = model
    kw = CASES[case]
    k, ring = kw.get("max_hops_per_step", 1), kw.get("from_ring")

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype),
        jax.eval_shape(lambda: init_stream(params, cfg, BATCH)),
    )
    lanes = spec((BATCH,), jnp.bool_ if k == 1 else jnp.int32)
    if ring is not None:
        inputs = (spec((BATCH, ring, cfg.hop), jnp.float32),
                  spec((BATCH,), jnp.int32), lanes)
    else:
        shape = (BATCH, cfg.hop) if k == 1 else (BATCH, k, cfg.hop)
        inputs = (spec(shape, jnp.float32), lanes)

    step = make_stream_hop(params, cfg, **kw)
    text = step.lower(state, *inputs).compile().as_text()

    if kw["backend"] == "pallas":
        assert "tpu_custom_call" in text
        # each kernel keeps its family's name: the device trace's readers
        # find the kernels by it
        kernels = set(re.findall(r"%(\w+_pallas)\.\d+ = [^\n]*custom-call\(", text))
        assert kernels == {"dilated_split_conv_pallas", "masked_matmul_pallas",
                           "linear_attention_step_pallas"}
    else:
        assert "tpu_custom_call" not in text
