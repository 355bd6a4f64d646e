"""Whole runs of the harness on the CPU at a small size, with the timed path
sound, broken underneath, and replaced by the control: ``correct`` must
come out true only for the sound one.

The harness's look for a chip is skipped; everything else is a whole run
through ``main()`` with a cell of ``BENCHMARK.json``, whose records per
call are cut to ``N`` so that the CPU holds the run.

    python -m pytest bench/
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import pytest

from bench import control, run, traffic

N = 1 << 16
CELLS = ["kv32_uniform.bulk", "k32_ands3.bulk", "kv32_uniform.small"]


def _state_unchanged(sort):
    return lambda keys, values: (keys, values)


def _half_left_out(sort):
    def broken(keys, values):
        h = keys.shape[0] // 2
        k, v = sort(keys[:h], None if values is None else values[:h])
        k = jnp.concatenate([k, keys[h:]])
        return k, None if values is None else jnp.concatenate([v, values[h:]])
    return broken


def _answer_altered(sort):
    """One record altered where it is produced: a key, or a value."""
    def broken(keys, values):
        k, v = sort(keys, values)
        if v is None:
            return k.at[N // 3].add(jnp.uint32(1)), None
        return k, v.at[N // 3].add(jnp.uint32(1))
    return broken


def _control(sort):
    return jax.jit(control.control_sort)


def _run_main(monkeypatch, capsys, cell, wrap):
    real_load = traffic.load_cell
    monkeypatch.setattr(run, "require_chip", lambda chips: jax.devices())
    monkeypatch.setattr(run, "load_peak", lambda kind: {"hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(run.traffic, "load_cell",
                        lambda name, b: real_load(name, b)._replace(n=N))
    real_entry = traffic.load_entry
    monkeypatch.setattr(
        run.traffic, "load_entry",
        lambda c, devices: (lambda e: e._replace(sort=wrap(e.sort)))(
            real_entry(c, devices)))
    assert run.main(["--workload", cell, "--seed", str(2**31 + 7),
                     "--seconds", "0.5", "--trace", "0"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(monkeypatch, capsys, cell):
    result = _run_main(monkeypatch, capsys, cell, lambda sort: sort)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert all(c == {"value": 0, "limit": 0}
               for c in result["checks"].values())


@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _answer_altered, _control])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(monkeypatch, capsys, cell, fault):
    result = _run_main(monkeypatch, capsys, cell, fault)
    assert result["correct"] is False
    assert result["failed"] >= min(result["attempted"], run.KEEP_CALLS)
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def test_no_chip_exits_nonzero(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) != 0
    assert capsys.readouterr().out.strip() == ""


def test_seed_words_keep_large_seeds_apart():
    words = {tuple(traffic.seed_words(s)) for s in
             (0, 7, 2**31 + 7, 2**32 + 7, 2**40)}
    assert len(words) == 5


def test_a_cell_of_new_files_only(monkeypatch, capsys, tmp_path):
    """A new key distribution is a generator and a configuration file, and
    its cell one entry of BENCHMARK.json: no file of bench/ is edited."""
    import os
    import shutil

    for kind in ("configs", "workloads", "keys", "entries", "metrics"):
        shutil.copytree(os.path.join(traffic.HERE, kind), tmp_path / kind)
    (tmp_path / "keys" / "constant.py").write_text(
        "import jax.numpy as jnp\n\n\n"
        "def make(rng, n, dtype, config):\n"
        "    return jnp.full((n,), config['value'], dtype)\n")
    (tmp_path / "configs" / "k32_constant.json").write_text(json.dumps(
        {"n": N, "key_dtype": "uint32", "value_dtype": "uint32",
         "keys": "constant", "value": 7}))
    benchmark = run.load_benchmark()
    benchmark["workloads"].append({"name": "k32_constant.bulk",
                                   "config": "k32_constant",
                                   "traffic": "bulk", "chips": 1})
    for m in benchmark["end_to_end"]:
        if m["name"] == "sort_rate":
            m["workloads"].append("k32_constant.bulk")
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    monkeypatch.setattr(run, "load_benchmark", lambda: benchmark)
    result = _run_main(monkeypatch, capsys, "k32_constant.bulk",
                       lambda sort: sort)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"sort_rate", "sort_mem_ratio",
                                      "setup_s"}
    result = _run_main(monkeypatch, capsys, "k32_constant.bulk",
                       _answer_altered)
    assert result["correct"] is False


def test_window_keeps_a_seeded_sample_and_counts_its_bytes():
    import random

    device = jax.devices()[0]
    entry = traffic.Entry(sort=lambda k, v: (jnp.copy(k), None),
                          sharding=None, counting_passes=None)
    pool = [(jnp.arange(1024, dtype=jnp.uint32), None)]
    samples = []
    for seed in (1, 2):
        calls, kept, during = run.window(entry, pool, 0.3, 3,
                                         random.Random(seed), [device])
        index = [i for i, _ in kept]
        assert len(calls) > 100 and index[:3] == [0, 1, 2]
        # about 3 * (1 + ln(calls / 3)) of them
        assert 3 < len(index) < 6 * (1 + math.log(len(calls)))
        # during the last call the sample held its outputs of 4 KiB each,
        # less the last call's own if it was drawn
        assert during == [4096 * sum(i < len(calls) - 1 for i in index)]
        samples.append(index)
    assert samples[0] != samples[1]
