"""Property-based build parity (dist/shard_index.py on-device build).

The pinned invariant: ``ShardedVectorIndex.build_sharded`` -- the ONE-program
on-device SPMD build -- produces bit-identical codes/postings per shard, and
bit-identical ``search`` results at ``page >= n_docs``, versus the reference
path ``VectorIndex.build`` + ``from_index``, for random
(n_docs, dims, shards, replicas, engine, index_best, merge) draws including
ragged tail shards.  The subprocess sweeps draw from a seeded
``random.Random``, so every run replays the same examples.

Multi-device sweeps run in a subprocess (the virtual-device flag must
precede jax initialisation, same pattern as test_shard_index.py): one
4-device and one 8-device mesh sweep, each covering even AND ragged splits
(two fixed anchor examples guarantee both) plus seeded random draws.
A separate subprocess pins the one-compiled-program claim: the df table's
``build_df_table`` is traced exactly once per build, for any shard count --
no per-shard host loop -- and ``build_postings`` only when the posting
lists are first read.
"""

import os
import subprocess
import sys

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import VectorIndex
from repro.dist.shard_index import ShardedVectorIndex
from repro.launch.mesh import make_shard_mesh

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_LEAVES = ("vectors", "codes", "post_docs", "post_codes", "df_table",
           "offsets", "live")


def _assert_same_index(ref, dev, ctx):
    for name in _LEAVES:
        a, b = np.asarray(getattr(ref, name)), np.asarray(getattr(dev, name))
        assert np.array_equal(a, b), (ctx, name)
    assert dev.seg_capacity == 0 and dev.n_appended == 0, ctx


@settings(max_examples=8, deadline=None)
@given(n_docs=st.integers(3, 40), dims=st.integers(4, 16),
       engine=st.sampled_from(["postings", "codes", "onehot", "codes_pallas"]),
       index_best=st.sampled_from([None, 3, 8]),
       merge=st.sampled_from(["gather", "stream"]),
       seed=st.integers(0, 2**20))
def test_build_parity_single_shard(n_docs, dims, engine, index_best, merge,
                                   seed):
    """S=1 runs in-process: the on-device build must already match the
    reference build leaf-for-leaf and search bit-for-bit."""
    rng = np.random.default_rng(seed)
    V = rng.normal(size=(n_docs, dims)).astype(np.float32)
    Q = rng.normal(size=(3, dims)).astype(np.float32)
    mesh = make_shard_mesh(1)
    single = VectorIndex.build(V, index_best=index_best)
    ref = ShardedVectorIndex.from_index(single, mesh)
    dev = ShardedVectorIndex.build_sharded(V, mesh, index_best=index_best)
    ctx = (n_docs, dims, engine, index_best, merge, seed)
    _assert_same_index(ref, dev, ctx)
    ids0, s0 = single.search(Q, k=5, page=2 * n_docs, engine=engine)
    ids2, s2 = dev.search(Q, k=5, page=2 * n_docs, engine=engine, merge=merge)
    assert np.array_equal(np.asarray(ids0), np.asarray(ids2)), ctx
    assert np.array_equal(np.asarray(s0), np.asarray(s2)), ctx


def test_builder_accepts_device_arrays():
    """The fixed host-round-trip: device-resident vectors build without a
    numpy copy and produce the same index as the host-array path."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    V = rng.normal(size=(17, 8)).astype(np.float32)
    mesh = make_shard_mesh(1)
    host = ShardedVectorIndex.build(V, mesh)
    dev = ShardedVectorIndex.build(jnp.asarray(V), mesh)
    _assert_same_index(host, dev, "device-resident build")


def _run_subprocess(script: str) -> None:
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd=_REPO)
    assert "OK" in out.stdout, out.stdout + out.stderr


def _sweep_script(n_devices, cells, n_examples, seed):
    """Subprocess source: seeded random parity sweep over ``cells`` =
    [(shards, replicas), ...] on an ``n_devices`` virtual mesh."""
    return rf"""
import os, sys, random
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
import numpy as np
from repro.core import VectorIndex
from repro.dist.shard_index import ShardedVectorIndex
from repro.launch.mesh import make_shard_mesh

rng = random.Random({seed})
cells = {cells!r}
engines = ["postings", "codes", "onehot", "codes_pallas"]

# anchors guarantee even AND ragged splits at the max shard count ...
smax = max(s for s, _ in cells)
examples = [(6 * smax, 8, cells[-1], "codes", None, "gather"),
            (6 * smax - 1, 8, cells[-1], "postings", 3, "stream")]
# ... then seeded random draws
for _ in range({n_examples}):
    examples.append((rng.randint(5, 48), rng.randint(4, 12),
                     rng.choice(cells), rng.choice(engines),
                     rng.choice([None, 3]), rng.choice(["gather", "stream"])))

for n_docs, dims, (s, r), engine, best, merge in examples:
    if s > n_docs:
        continue
    vrng = np.random.default_rng(hash((n_docs, dims, s, r)) % 2**32)
    V = vrng.normal(size=(n_docs, dims)).astype(np.float32)
    Q = vrng.normal(size=(3, dims)).astype(np.float32)
    mesh = make_shard_mesh(s, r)
    single = VectorIndex.build(V, index_best=best)
    ref = ShardedVectorIndex.from_index(single, mesh)
    dev = ShardedVectorIndex.build_sharded(V, mesh, index_best=best)
    ctx = (n_docs, dims, s, r, engine, best, merge)
    for name in {_LEAVES!r}:
        a = np.asarray(getattr(ref, name))
        b = np.asarray(getattr(dev, name))
        assert np.array_equal(a, b), (ctx, name)
    ids0, s0 = single.search(Q, k=5, page=2 * n_docs, engine=engine)
    ids2, s2 = dev.search(Q, k=5, page=2 * n_docs, engine=engine, merge=merge)
    assert np.array_equal(np.asarray(ids0), np.asarray(ids2)), ctx
    assert np.array_equal(np.asarray(s0), np.asarray(s2)), ctx
print("OK")
"""


def test_build_parity_sweep_4dev():
    """Random (n_docs, dims, shards, replicas, engine, index_best, merge)
    sweep on a 4-virtual-device mesh, all shard layouts that fit."""
    _run_subprocess(_sweep_script(
        4, [(1, 1), (2, 1), (2, 2), (4, 1)], n_examples=6, seed=401))


def test_build_parity_sweep_8dev():
    """The same sweep on an 8-virtual-device mesh, replica tiers included."""
    _run_subprocess(_sweep_script(
        8, [(2, 4), (4, 2), (8, 1)], n_examples=4, seed=801))


def test_build_is_one_compiled_program():
    """``build_sharded`` (and the loop-free ``from_index``) trace the df
    table's ``build_df_table`` exactly ONCE regardless of shard count, and
    ``build_postings`` never: the build is one compiled SPMD program, not
    an S-iteration host loop, and sorts no posting list.  Reading the
    posting lists then traces ``build_postings`` once, in one program too.
    Fresh shapes guarantee a fresh trace (jit caching would otherwise hide
    calls)."""
    _run_subprocess(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import repro.dist.shard_index as si
from repro.core import VectorIndex
from repro.launch.mesh import make_shard_mesh

calls, tables = [], []
orig, orig_table = si.build_postings, si.build_df_table
si.build_postings = lambda c: (calls.append(1), orig(c))[1]
si.build_df_table = lambda *a: (tables.append(1), orig_table(*a))[1]

V = np.random.default_rng(3).normal(size=(37, 9)).astype(np.float32)
mesh = make_shard_mesh(4)
dev = si.ShardedVectorIndex.build_sharded(V, mesh)
assert len(tables) == 1, f"build_sharded traced build_df_table {len(tables)}x"
assert not calls, f"build_sharded traced build_postings {len(calls)}x"
dev.post_docs
assert len(calls) == 1, f"post_docs traced build_postings {len(calls)}x"

calls.clear()
tables.clear()
si.ShardedVectorIndex.from_index(VectorIndex.build(V[:35, :8]), mesh)
assert len(tables) == 1, f"from_index traced build_df_table {len(tables)}x"
assert not calls, f"from_index traced build_postings {len(calls)}x"
print("OK")
""")
