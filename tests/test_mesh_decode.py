"""Mesh-wired decode cell: engine parity and metadata placement plumbing.

The test suite runs single-device, so the mesh here is (1, n) — the full 2×4
multi-device parity + ledger gate lives in ``benchmarks/mesh_decode.py`` and
the ``sharded_decode`` analysis target (CI's mesh-decode job runs both under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  What *is* real at
any mesh size: the wiring path (tp_shard_plan → pair_params(shards=…) →
pairing_axes → paired_shardings_for → pjit), and that it decodes the same
tokens as the single-host engine.  One test starts a four-device CPU process
of its own, so that the kernels' multi-device branch (``kernels.ops.on_mesh``)
runs on paired weights.
"""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm as M
from repro.models.param import unzip
from repro.parallel.sharding import auto_mesh
from repro.serving.engine import ServeEngine

KNOBS = M.PerfKnobs(
    q_chunk=16, k_chunk=16, remat="none",
    gemm="pallas_paired", pair_block_n=1, pair_rounding=0.0,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_smoke_config("qwen2-1.5b"), dtype="float32")
    params, _ = unzip(M.init_lm(cfg, jax.random.key(0)))
    return cfg, params


def _mesh():
    return auto_mesh((1, jax.device_count()), ("data", "model"))


def test_mesh_engine_token_parity_r0(tiny):
    cfg, params = tiny
    prompts = {0: np.arange(1, 8, dtype=np.int32)}
    ref = ServeEngine(cfg, params, max_seq=24, batch_size=2, knobs=KNOBS)
    eng = ServeEngine(
        cfg, params, max_seq=24, batch_size=2, knobs=KNOBS, mesh=_mesh()
    )
    out_ref = ref.generate(dict(prompts), 5)
    out_mesh = eng.generate(dict(prompts), 5)
    assert out_ref[0] == out_mesh[0]


def test_wire_serve_cell_pairs_and_places(tiny):
    from repro.launch.steps import wire_serve_cell

    cfg, params = tiny
    cell = wire_serve_cell(
        cfg, params, _mesh(), batch_size=2, max_seq=24, knobs=KNOBS
    )
    assert cell.pair_report is not None
    # every paired leaf carries its shard provenance in the report
    assert len(cell.pair_report.leaves) == len(cfg.paired_leaves)
    seg = cell.params["segments"][0]
    assert "wq_pairing" in seg["attn"]
    # metadata sharding mirrors the weight's resolved spec: the wq block
    # axis rides on `model` (size n divides the smoke head dims)
    wq_spec = cell.p_shard["segments"][0]["attn"]["wq"].spec
    meta_spec = cell.p_shard["segments"][0]["attn"]["wq_pairing"]["I"].spec
    assert meta_spec[1] == wq_spec[2]
    # params were device_put against those shardings
    assert jax.tree.leaves(cell.params)[0].committed


def test_mesh_engine_add_release_cycle(tiny):
    """Slot lifecycle works on sharded cache arrays (splice/scrub paths)."""
    cfg, params = tiny
    eng = ServeEngine(
        cfg, params, max_seq=24, batch_size=2, knobs=KNOBS, mesh=_mesh()
    )
    eng.add_request(0, np.arange(1, 6, dtype=np.int32))
    eng.step()
    eng.release_slot(0)
    assert eng.free_slots() == [0, 1]
    eng.add_request(0, np.arange(1, 4, dtype=np.int32))
    eng.step()


#: run in a process with four CPU devices: chip_smoke's mesh phase (the
#: ``--four-chip`` path) at a rounding that pairs most of the smoke-size
#: weights, counting the shard_map launches the kernels go through
_FOUR_DEVICE_MESH = """
import sys
import jax

launches = []
_shard_map = jax.shard_map
def counting(*a, **k):
    launches.append(1)
    return _shard_map(*a, **k)
jax.shard_map = counting

import chip_smoke
from repro.configs import get_smoke_config
from repro.launch.serve import serving_params

assert jax.device_count() == 4, jax.devices()
cfg = get_smoke_config("qwen2-1.5b")
chip_smoke.mesh_phase(cfg, serving_params(cfg), 0.25, seed=0, max_seq=64,
                      request_kw=dict(n=4, prompt_len=(16, 25), new_tokens=4))
assert launches, "no kernel went through the mesh branch"
print("launches", len(launches))
"""


def test_four_device_mesh_runs_paired_kernels_in_shard_map():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(root / "src"), str(root)]))
    run = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_MESH], cwd=root,
                         env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-3000:]
    out = run.stdout
    assert "per TP shard" in out and "paired 0 " not in out, out
    assert "answered 4/4" in out and "launches" in out, out
