"""Rehearsal compiles: the main path's Pallas kernels, AOT-compiled for a
described (not attached) TPU v5e at Amazon-670K widths.

Interpret mode accepts block shapes and memory layouts that the TPU's
Mosaic compiler refuses, so the CPU kernel tests alone cannot show that a
kernel will run on the chip. Each test here lowers a kernel natively
(``interpret=False``) against one device of a ``v5e:2x2`` topology
description and asserts that the compiled program contains the Mosaic
kernel (``tpu_custom_call``). Nothing runs, so results and times are not
checked here.

The topology is described only inside a module fixture: loading the TPU
library while a module is imported would make pytest-xdist workers collect
different tests.
"""
from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.data.xml_synth import AMAZON_670K
from repro.kernels.spmm.ops import _fold_vmap
from repro.kernels.spmm.spmm import spmm_grad_w_replicated, spmm_replicated
from repro.kernels.weighted_merge.weighted_merge import weighted_merge

NF = AMAZON_670K["n_features"]
NC = AMAZON_670K["n_classes"]
H = 128           # configs/archs.py XML_WORKLOADS hidden width
B, K, R = 256, 256, 4  # b_max, padded nnz slots, replicas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("replicas", [None, R], ids=["plain", "vmap_r4"])
def test_spmm_compiles_for_v5e(shape, replicas):
    """The forward gather, alone and under vmap over the replicas (the
    trainer's round body), at the batch the trainer packs."""
    call = _fold_vmap(functools.partial(spmm_replicated, interpret=False))
    fn = lambda i, v, m, w: call(i[None], v[None], m[None], w[None])[0]
    lead = () if replicas is None else (replicas,)
    if replicas is not None:
        fn = jax.vmap(fn)
    _assert_kernel(
        fn,
        shape(lead + (B, K), jnp.int32),
        shape(lead + (B, K), jnp.float32),
        shape(lead + (B, K), jnp.bool_),
        shape(lead + (NF, H), jnp.float32),
    )


def test_spmm_grad_w_compiles_for_v5e(shape):
    fn = functools.partial(spmm_grad_w_replicated, n_rows=NF, interpret=False)
    _assert_kernel(
        fn,
        shape((1, B, K), jnp.int32),
        shape((1, B, K), jnp.float32),
        shape((1, B, K), jnp.bool_),
        shape((1, B, H), jnp.float32),
    )


def test_sharded_eval_compiles_for_v5e_mesh(topo, monkeypatch):
    """Under --placement sharded the global model is replicated over a
    four-chip replica mesh; the compiler cannot partition the model's
    Pallas input layer, so the trainer's eval must run it per shard."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.base import ElasticConfig
    from repro.core.trainer import ElasticTrainer
    from repro.data.providers import SparseProvider
    from repro.data.xml_synth import make_xml_dataset
    from repro.kernels.spmm import ops as spmm_ops
    from repro.models.xml_mlp import XMLMLPConfig, make_model
    from repro.optim.sgd import SGDConfig
    from repro.sharding.rules import REPLICA_AXIS

    # native kernels, as on the chip (the backend here is the CPU)
    monkeypatch.setattr(spmm_ops, "_interpret_mode", lambda: False)
    spmm_ops._spmm_call.cache_clear()
    ds = make_xml_dataset(n_samples=256, n_features=2048, n_classes=512)
    provider = SparseProvider.make(ds, seed=0)
    model = make_model(XMLMLPConfig(n_features=2048, n_classes=512,
                                    use_spmm_kernel=True))
    mesh = Mesh(np.asarray(topo.devices[:R]), (REPLICA_AXIS,))
    trainer = ElasticTrainer(
        model=model, provider=provider, sgd=SGDConfig(), base_lr=0.05,
        seed=0, mesh=mesh,
        cfg=ElasticConfig.from_bmax(32, algorithm="adaptive", n_replicas=R,
                                    placement="sharded"),
    )
    replicated = NamedSharding(mesh, P())
    params = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=replicated),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
    )
    batch = {
        k: jax.ShapeDtypeStruct(s[2:], d, sharding=replicated)
        for k, (s, d) in provider.staging_spec(1, 1, 32).items()
    }
    try:
        text = trainer._eval.lower(params, batch).compile().as_text()
    finally:
        spmm_ops._spmm_call.cache_clear()
    assert "tpu_custom_call" in text


def _while_body_ops(text: str) -> list[str]:
    """Instructions of the compiled program's ``while`` bodies and of every
    computation they call, as lines of the optimised HLO text."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line.strip())
    todo = [re.search(r"body=%?([\w.\-]+)", op).group(1)
            for ops in comps.values() for op in ops if " while(" in op]
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            for op in comps[c]:
                todo += re.findall(
                    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", op)
    return [op for c in seen for op in comps[c]]


def test_megabatch_w1_scatter_updates_in_place(shape, monkeypatch):
    """The trainer's mega-batch program (vmap placement, R=4) at Amazon
    widths, whose odd feature count is stored rounded up to the sublane
    tile: the replica-batched w1 scatter flattens the scan's w1 carry to
    (R * rows, H) with a bitcast, and no whole-w1 ``copy`` or ``reshape``
    runs in the loop (DESIGN.md §3)."""
    from repro.configs.base import ElasticConfig
    from repro.core.trainer import ElasticTrainer
    from repro.data.providers import SparseProvider
    from repro.data.xml_synth import make_xml_dataset
    from repro.kernels.spmm import ops as spmm_ops
    from repro.models.xml_mlp import XMLMLPConfig, make_model
    from repro.optim.sgd import SGDConfig

    assert NF % 8  # the case the padding is for
    # native kernels, as on the chip (the backend here is the CPU)
    monkeypatch.setattr(spmm_ops, "_interpret_mode", lambda: False)
    spmm_ops._spmm_call.cache_clear()
    b_max, n_rounds = 32, 2
    provider = SparseProvider.make(
        make_xml_dataset(n_samples=256, n_features=NF, n_classes=NC), seed=0)
    model = make_model(XMLMLPConfig(n_features=NF, n_classes=NC, hidden=H,
                                    use_spmm_kernel=True))
    trainer = ElasticTrainer(
        model=model, provider=provider, sgd=SGDConfig(), base_lr=0.05, seed=0,
        cfg=ElasticConfig.from_bmax(b_max, algorithm="adaptive", n_replicas=R),
    )
    replicas = jax.tree_util.tree_map(
        lambda l: shape((R,) + l.shape, l.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
    )
    batches = {k: shape(s, d) for k, (s, d)
               in provider.staging_spec(n_rounds, R, b_max).items()}
    try:
        text = trainer._megabatch.lower(
            replicas, None, batches, shape((R,), jnp.float32),
            shape((n_rounds, R), jnp.float32), transforms=trainer._transforms,
        ).compile().as_text()
    finally:
        spmm_ops._spmm_call.cache_clear()

    rows = replicas["w1"].shape[1]
    assert rows == -(-NF // 8) * 8
    w1_shapes = (f"f32[{R},{rows},{H}]", f"f32[{rows},{R},{H}]",
                 f"f32[{R * rows},{H}]")
    made = {}  # opcode -> shapes it produces on w1's bytes in the loop
    for op in _while_body_ops(text):
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (\w+\[[\d,]*\])\S* ([\w\-]+)\(",
                     op)
        if m and m.group(1) in w1_shapes:
            made.setdefault(m.group(2), set()).add(m.group(1))
    assert "copy" not in made and "reshape" not in made, made
    assert w1_shapes[2] in made.get("bitcast", ()), made


@pytest.mark.parametrize("n", [NF * H, H * NC], ids=["w1", "w2"])
def test_weighted_merge_momentum_compiles_for_v5e(shape, n):
    """Algorithm 2's merge with the momentum term over R replicas of one
    parameter leaf."""
    fn = functools.partial(weighted_merge, gamma=0.9, interpret=False)
    _assert_kernel(
        fn,
        shape((R, n), jnp.float32),
        shape((R,), jnp.float32),
        shape((n,), jnp.float32),
        shape((n,), jnp.float32),
    )


@pytest.fixture
def merge_ops(topo, monkeypatch):
    """``kernels/weighted_merge/ops`` lowering natively, with each leaf's
    layout read from the described chip, as on a v5e."""
    import numpy as np
    from jax.experimental.layout import Layout

    from repro.kernels.weighted_merge import ops

    dev = topo.devices[0]
    monkeypatch.setattr(ops, "_interpret_mode", lambda: False)
    monkeypatch.setattr(ops, "stored_order", lambda s, dtype: tuple(
        Layout.from_pjrt_layout(dev.client.get_default_layout(
            np.dtype(dtype), tuple(s), dev)).major_to_minor))
    return ops


@pytest.mark.parametrize("replicas,gamma", [(R, 0.9), (1, 0.0)],
                         ids=["one_chip_r4", "four_chips_shard"])
def test_weighted_merge_reads_leaves_as_stored_for_v5e(
        merge_ops, shape, replicas, gamma):
    """The merge of the XML model's four leaves at Amazon-670K widths, R
    replicas on a chip with the momentum term fused, or one replica (a
    shard on four chips) without it: nothing w2-sized is made but the
    kernels' outputs and bitcasts. A v5e stores w2's replicas (R, H, C) as
    (C, R, H); reading them in another order would copy them."""
    import numpy as np

    leaves = {"w1": (-(-NF // 8) * 8, H), "b1": (H,), "w2": (H, NC),
              "b2": (NC,)}
    reps = {k: shape((replicas,) + s, jnp.float32) for k, s in leaves.items()}
    glob = {k: shape(s, jnp.float32) for k, s in leaves.items()}
    fn = lambda x, a, g, gp: merge_ops.merge_pytree(x, a, g, gp, gamma)
    text = jax.jit(fn).lower(
        reps, shape((replicas,), jnp.float32), glob, glob
    ).compile().as_text()

    made = {}  # opcode -> names of the w2-sized arrays it makes
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m and np.prod([int(d) for d in m.group(2).split(",") if d]) >= H * NC:
            made.setdefault(m.group(3), []).append(m.group(1))
    assert set(made) <= {"parameter", "bitcast", "custom-call"}, made
    assert [n for n in made["custom-call"]
            if not n.startswith("weighted_merge")] == [], made


@pytest.mark.parametrize("leaf", [(R, H, NC), (R, 22, 2048), (R, 1001),
                                  (R, 1)], ids=str)
def test_weighted_merge_bfloat16_compiles_for_v5e(merge_ops, shape, leaf):
    """bfloat16 leaves: replicas on the sublanes (w2's (C, R, H)), a
    stacked-layer vector, a bias and a column, which the kernel flattens
    first; each within the chip's scoped VMEM."""
    fn = lambda x, a, g, gp: merge_ops.merge(x, a, g, gp, 0.9)
    _assert_kernel(
        fn,
        shape(leaf, jnp.bfloat16),
        shape((R,), jnp.float32),
        shape(leaf[1:], jnp.bfloat16),
        shape(leaf[1:], jnp.bfloat16),
    )
