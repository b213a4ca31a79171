"""Sandbox rehearsal: each cell's largest token budget of the mixed step,
compiled for a described (not attached) v5e at the cell's real shapes,
must fit the chip with the pools counted.  Run before a chip call:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearse_v5e.py -s

Slow (it builds each model at full size on the host): minutes, not part of
the repo's tier-1 tests.  The topology is described inside a fixture, never
at import.
"""
import gc
import json
import os

import pytest
from jax.sharding import SingleDeviceSharding

from tinytree import ROOT

HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e!r}")
    return SingleDeviceSharding(topo.devices[0])


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _report(workload, what, mem):
    need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"\n{workload}: {what}: "
          f"arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temp {mem.temp_size_in_bytes / 2**30:.2f} GiB, "
          f"output {mem.output_size_in_bytes / 2**30:.2f} GiB, "
          f"alias {mem.alias_size_in_bytes / 2**30:.2f} GiB, "
          f"need {need / 2**30:.2f} GiB")
    return need


@pytest.mark.parametrize("workload", _cells())
def test_top_budget_fits(v5e, workload):
    from harness import program, spec
    cell = spec.Cell(workload, ROOT)
    model = program.build_model(cell, seed=0)
    eng = program.build_engine(
        model, dict(cell.deploy["engine"], use_pallas=True))
    top = eng.token_budgets[-1]
    lowered = eng.mixed.aot_lower(top, device_sharding=v5e)
    text = lowered.as_text()
    # the Pallas kernels the cell's step must launch: its file may name
    # its own (``kernels``)
    for name in cell.deploy.get("kernels", ("ragged_paged_attention",
                                            "rope_qkv_epilogue")):
        assert f'kernel_name = "{name}"' in text
    need = _report(workload, f"budgets {eng.token_budgets}, top {top}",
                   lowered.compile().memory_analysis())
    del eng, model, lowered
    gc.collect()
    assert need <= HBM_BYTES
