"""What one cell is, and the one traffic generator that feeds it.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  Each
is a data file, found by its name, and each names the code it needs, found
the same way, so a new cell is new files and entries only:

* ``bench/configs/<config>.json``: the records (``key_dtype``, and
  ``value_dtype`` or null for keys only), the records per sort ``n``, and
  ``keys``, the key generator ``bench/keys/<keys>.py``, whose
  ``make(rng, n, dtype, config)`` reads its own parameters from the
  configuration;
* ``bench/workloads/<traffic>.json``: the loop (closed, one caller), the
  ``pool`` of distinct inputs, an optional ``n`` that overrides the
  configuration's, and ``entry``, the timed path
  ``bench/entries/<entry>.py``, whose ``build(cell, devices)`` returns an
  ``Entry``.

The pool is made in one jitted call from ``--seed``, placed where the entry
wants it; the same seed gives the same inputs.  Values are row ids
``0..n-1`` in the value type.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class Cell(NamedTuple):
    name: str
    n: int                       # records per call, over all chips
    key_dtype: str
    value_dtype: Optional[str]   # None: keys only
    keys: str                    # key generator, bench/keys/<keys>.py
    entry: str                   # timed path, bench/entries/<entry>.py
    pool: int                    # distinct inputs the loop cycles over
    chips: int
    config: dict                 # the configuration file, for generators

    @property
    def with_values(self) -> bool:
        return self.value_dtype is not None

    @property
    def key_bytes(self) -> int:
        return np.dtype(self.key_dtype).itemsize

    @property
    def value_bytes(self) -> int:
        return np.dtype(self.value_dtype).itemsize if self.with_values else 0

    @property
    def record_bytes(self) -> int:
        return self.key_bytes + self.value_bytes

    def __repr__(self) -> str:
        return (f"Cell({self.name}: n={self.n} {self.key_dtype} keys "
                f"({self.keys}), values={self.value_dtype}, "
                f"entry={self.entry}, pool={self.pool}, chips={self.chips})")


def _host_records(out) -> tuple:
    return tuple(None if o is None else np.asarray(o) for o in out)


class Entry(NamedTuple):
    """The timed path of a cell, as its ``bench/entries`` module builds it."""
    sort: Callable        # (keys, values or None) -> its outputs, a pytree
    sharding: Any         # where the pool's arrays are placed
    counting_passes: Optional[Callable]  # (keys, values or None) -> int,
                                         # what the program reports
    to_host: Callable = _host_records    # outputs -> (keys, values or None)
                                         # as NumPy arrays, after the window


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def load_cell(name: str, benchmark: dict) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, from its two data files."""
    entry = next((w for w in benchmark["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = _load("configs", entry["config"])
    mix = _load("workloads", entry["traffic"])
    if mix["loop"] != "closed" or mix["callers"] != 1:
        raise ValueError(f"{entry['traffic']}: only a closed loop with one "
                         "caller is generated")
    return Cell(name=name, n=int(mix.get("n", cfg["n"])),
                key_dtype=cfg["key_dtype"], value_dtype=cfg["value_dtype"],
                keys=cfg["keys"], entry=mix["entry"], pool=int(mix["pool"]),
                chips=int(entry["chips"]), config=cfg)


def load_entry(cell: Cell, devices: list) -> Entry:
    return load_module("entries", cell.entry).build(cell, devices)


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words (no two seeds collide)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned integer")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def make_pool(cell: Cell, seed: int, sharding):
    """``cell.pool`` inputs as a list of ``(keys, values or None)``, each a
    separate device array placed by ``sharding``, made by one jitted call
    from ``seed``."""
    make_keys = load_module("keys", cell.keys).make
    key_dtype = jnp.dtype(cell.key_dtype)

    def make(words):
        rng = jax.random.fold_in(jax.random.key(words[0]), words[1])
        out = []
        for sub in jax.random.split(rng, cell.pool):
            out.append(make_keys(sub, cell.n, key_dtype, cell.config))
            if cell.with_values:
                out.append(jnp.arange(cell.n, dtype=cell.value_dtype))
        return out

    arrays = jax.block_until_ready(
        jax.jit(make, out_shardings=sharding)(seed_words(seed)))
    if not cell.with_values:
        return [(k, None) for k in arrays]
    return [(arrays[i], arrays[i + 1]) for i in range(0, len(arrays), 2)]


def host_pool(pool) -> list:
    """The pool copied to host memory, for the reference."""
    return [(np.asarray(k), None if v is None else np.asarray(v))
            for k, v in pool]
