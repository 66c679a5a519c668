"""Ahead-of-time compiles for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode and the CPU backend accept:
Mosaic layouts, tiling, VMEM.  These tests compile the Pallas arbitration
kernels at the widths of the ``headline_a2a.json`` MRLS fabrics (1k and
104,976 endpoints, computed from the spec parameters, never built) and the
engine's step chunk of the tiny golden fabric for one described chip.
Nothing runs: a pass here says the chip's compiler accepts the program,
not that it is correct or fast.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.api import RouteSpec
from repro.core import build_tables, mrls
from repro.kernels.switch_arb import ops as arb_ops
from repro.kernels.switch_arb.kernel import switch_arbitrate, vc_prearb
from repro.simulator.engine import SimConfig, Simulator, Traffic

ROOT = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((ROOT / "examples" / "specs" / "headline_a2a.json")
                  .read_text())
GOLDEN = json.loads((ROOT / "tests" / "golden" / "engine_parity.json")
                    .read_text())


def _mrls_widths(name):
    """(switches N, ports P, requester rows R, VCs V) of a spec'd MRLS."""
    exp = next(e for e in SPEC["experiments"] if e["name"] == name)
    p = exp["network"]["params"]
    n1, u, d = p["n_leaves"], p["u"], p["d"]
    ports = u + d
    vcs = exp["route"].get("vcs", RouteSpec().vcs)
    return n1 + u * n1 // ports, ports, ports + d, vcs


WIDTHS = ("headline.1k.mrls", "headline.100k.mrls")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("name", WIDTHS)
def test_vc_prearb_compiles_for_v5e(one_chip, name):
    n, p, _, v = _mrls_widths(name)
    qlen = _sds((n, p, v), jnp.int32, one_chip)
    rand = _sds((n, p, v), jnp.float32, one_chip)
    compiled = vc_prearb.lower(qlen, rand, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", WIDTHS)
def test_switch_arbitrate_compiles_for_v5e(one_chip, name):
    n, p, r, _ = _mrls_widths(name)
    i32 = jnp.int32
    rp = [_sds((n, r, p), dt, one_chip) for dt in (i32, i32, i32,
                                                   jnp.float32)]
    rows = [_sds((n, r), i32, one_chip) for _ in range(3)]
    compiled = switch_arbitrate.lower(*rp, *rows, penalty=8.0,
                                      interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_engine_chunk_compiles_for_v5e(one_chip, monkeypatch, backend):
    # the kernels pick interpret mode from the process's backend, which is
    # the CPU here; the chip they are compiled for takes the compiled path
    monkeypatch.setattr(arb_ops, "_auto_interpret", lambda: False)
    tables = build_tables(mrls(**GOLDEN["fabric"]))
    tr = Traffic("uniform", load=0.7)
    with Simulator(tables, SimConfig(policy="polarized", max_hops=10,
                                     pool=4096, backend=backend)) as sim:
        as_sds = lambda x: _sds(x.shape, x.dtype, one_chip)   # noqa: E731
        st = jax.tree.map(as_sds, sim.make_state(tr, seed=0))
        tb = jax.tree.map(as_sds, sim._tables())
        compiled = Simulator._run_chunk_jit.lower(sim, st, tb, tr,
                                                  16).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")
    assert compiled.memory_analysis().argument_size_in_bytes > 0
