"""The comparison that decides ``correct``, at the configurations' smoke
sizes on the CPU, through the same harness as a run on the chip.

- The reference's own compression of the seeded weights equals what the
  program serves (its ``densify`` of ``_to_sparse_weight``), to bf16
  rounding, though the reference imports nothing of the program.
- A sound run is correct; the control (the reference in float8, the
  precision below the configuration's bfloat16) is not, judged by the same
  ``check.result``; nor is the program serving its own int8 N:M values,
  whose stored bytes are not the configuration's.
- A run with the timed path broken underneath is not correct: a token
  altered where it is produced, and a decode step that returns the KV
  state it was given unchanged.
- Without a TPU the timed path exits non-zero and prints no result.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, reference, registry, weights
from bench import run

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in registry.benchmark()["workloads"]]


@pytest.mark.parametrize("shape", [(64, 512), (48, 256)])
def test_reference_compression_equals_served_weight(shape):
    from repro.models.sparse_serving import densify
    conf = registry.config("internlm2-1.8b.s816")
    w = (jax.random.normal(jax.random.PRNGKey(3), shape)
         / np.sqrt(shape[1])).astype(jnp.bfloat16)
    served = densify(harness.compressor(conf["weights"])(w))
    ref = reference.compress(w, conf["weights"])
    np.testing.assert_allclose(np.asarray(served, np.float32),
                               np.asarray(ref), rtol=0, atol=2e-3)
    # the same positions are zero in both
    assert np.array_equal(np.asarray(served) == 0, np.asarray(ref) == 0)


def _run(cell, seed, control=False, plant=None, monkeypatch=None,
         served=None):
    run.setup_jax(cache=False)
    wl, conf, mix, peaks = run.load_cell(cell, rehearse=True)
    if plant is not None:
        build = harness.build

        def planted(conf, seed):
            engine, params = build(conf, seed)
            plant(engine)
            return engine, params
        monkeypatch.setattr(harness, "build", planted)
    res = run.serve(wl, run.merged(conf, served or {}), mix, seed, 2.0,
                    False, None)
    return run.check_served(res, conf, seed, control=control), conf


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell):
    res, conf = _run(cell, 123456789012, control=True)
    r = res["reading"]
    assert res["correct"], r
    assert r["tokens"] >= conf["check"]["min_tokens"]
    assert res["checked"]["weight_bytes"]["value"] == \
        res["checked"]["weight_bytes"]["limit"]
    assert not res["control_correct"], r["control"]
    assert r["control"]["widest_gap"] > conf["check"]["widest_gap"], r


def test_int8_values_are_not_the_stated_model():
    res, conf = _run("internlm2-s816.chat", 5,
                     served={"weights": {"values": "int8"}})
    assert not res["correct"], res["checked"]
    wb = res["checked"]["weight_bytes"]
    assert wb["value"] < wb["limit"]


@pytest.mark.parametrize("values", ["bfloat16", "int8"])
def test_weight_bytes_at_the_cell_size(values):
    """At the configuration's own sizes (shapes only), the served tree
    holds the stated bytes; with int8 values it holds fewer."""
    conf = registry.config("internlm2-1.8b.s816")
    served = run.merged(conf, {"weights": {"values": values}})
    tree = jax.eval_shape(
        weights.params_fn(served["model"],
                          harness.compressor(served["weights"])),
        weights.base_key(1))
    reading = {"tokens": 100, "widest_gap": 0.0,
               "weight_bytes": check.tree_bytes(tree)}
    ok, checked = check.result(reading, conf)
    assert ok == (values == "bfloat16"), checked


def _altered_token(engine):
    """Every decoded token is the one after the model's choice."""
    dec = engine.adapter.step_decode

    def step_decode(tokens, active):
        return jnp.roll(dec(tokens, active), 1, axis=-1)
    engine.adapter.step_decode = step_decode


def _state_unchanged(engine):
    """The decode step hands back the KV arenas it was given, so no
    decoded token's keys and values are kept."""
    a = engine.adapter
    fn = a._decode_fn
    a._decode_fn = jax.jit(lambda p, k, v, *rest: (fn(p, k, v, *rest)[0],
                                                   (k, v)))


@pytest.mark.parametrize("plant", [_altered_token, _state_unchanged])
def test_broken_timed_path_is_not_correct(plant, monkeypatch):
    res, conf = _run("internlm2-s816.chat", 77, plant=plant,
                     monkeypatch=monkeypatch)
    assert not res["correct"], res["reading"]
    assert res["reading"]["widest_gap"] > conf["check"]["widest_gap"]


def test_timed_path_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_result_line_names_its_device_and_checks_last():
    res = {"correct": True, "attempted": 3, "failed": 0,
           "e2e": {"tok_per_s": 1.0, "itl_p50_ms": 2.0, "itl_p95_ms": 3.0,
                   "setup_s": 4.0, "ttft_p95_ms": 5.0},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 7},
           "checked": {"widest_gap": {"value": 0.1, "limit": 0.5}}}
    wl = registry.workload(CELLS[0])
    line = run.result_line(wl, res, trace=False, rehearse=False)
    assert list(line)[-1] == "checked"
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 7}
    e2e, _ = registry.metrics_for(CELLS[0])
    assert set(line["metrics"]) == {m["name"] for m in e2e}
