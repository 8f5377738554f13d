"""The main path's Pallas kernels compiled for a described TPU v5e chip, and
the platform rule that picks how the kernels run.

The TPU compiler ships with jax, so a v5e can be *described* and compiled
for without one attached: what Mosaic refuses here (unaligned blocks,
scalar stores to VMEM, too much VMEM) it would refuse on the chip. Nothing
runs, so these tests say nothing about results or speed. The topology is
described inside a module fixture, never at import time: only one process
may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.kernels.attention import ops as att_ops
from repro.kernels.cka import ops as cka_ops
from repro.kernels.cka import ref as cka_ref


@pytest.fixture(scope="module")
def no_compile_cache():
    """A TPU program written to the persistent cache cannot be read back
    without a chip, so the cache stays off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_compile_cache):
    """One chip of a described v5e:2x2 host (skips where none can be
    described)."""
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else the compiler logs to /tmp
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure here means it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---------------------------------------------------------------------------
# compiles for the chip


@pytest.mark.parametrize("n,d", [(16, 131072), (2048, 768)],
                         ids=["mobilenetv2-probe", "bert-base-tokens"])
def test_cka_kernel_compiles_for_v5e(one_chip, n, d):
    """SimFreeze's CKA probe at MobileNetV2's widest probe activation
    (16 images x 64*64*32 features at 128 px) and at BERT-base token width
    (16 x 128 tokens x 768)."""
    x = _spec((n, d), one_chip)
    compiled = cka_ops.cka_terms.lower(x, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    """The `use_pallas` serving forward at BERT-base width: batch 16, 128
    tokens, 12 heads of 64."""
    q = _spec((16, 128, 12, 64), one_chip)
    compiled = att_ops.flash_attention.lower(
        q, q, q, causal=False, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# the platform rule, on the CPU backend the tests run on


def test_cka_default_is_interpret_mode_on_cpu_and_matches_ref():
    assert jax.default_backend() == "cpu"
    assert kernels.resolve_interpret() is True
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(96, 640)), jnp.float32)
    y = jnp.asarray(0.4 * np.asarray(x) + rng.normal(size=(96, 640)),
                    jnp.float32)
    assert "tpu_custom_call" not in cka_ops.cka_terms.lower(x, y).as_text()
    got = float(cka_ops.cka(x, y))
    xc = x - x.mean(0)
    yc = y - y.mean(0)
    np.testing.assert_allclose(got, float(cka_ref.cka_ref(xc, yc)),
                               rtol=1e-4)


@pytest.mark.parametrize("backend,override,want", [
    ("tpu", None, False), ("cpu", None, True),
    ("tpu", True, True), ("cpu", False, False), ("gpu", True, True)])
def test_resolve_interpret_follows_backend(monkeypatch, backend, override,
                                           want):
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: backend)
    assert kernels.resolve_interpret(override) is want


def test_resolve_interpret_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' backend"):
        kernels.resolve_interpret()
