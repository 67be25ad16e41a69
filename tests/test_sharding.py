"""Sharding rule engine: divisibility fallbacks, spec construction."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (
    Rules, DEFAULT_RULE_TABLE, logical_to_spec, spec_bytes, tree_shardings,
)


@pytest.fixture(scope="module")
def mesh():
    # 1 real device: build an abstract mesh over a fake axis layout.
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def _rules(mesh):
    return Rules(mesh=mesh, table=dict(DEFAULT_RULE_TABLE))


def test_divisible_dims_shard(mesh):
    r = _rules(mesh)
    spec = logical_to_spec(("embed_fsdp", "ffn"), (7168, 19200), r)
    assert spec == P(("data",), ("model",))


def test_indivisible_dims_replicate(mesh):
    r = _rules(mesh)
    # deepseek: 56 heads on 16-way model axis -> replicated
    spec = logical_to_spec(("batch", "seq", "heads", "head_dim"),
                           (16, 4096, 56, 128), r)
    assert spec[2] is None
    # mixtral: 8 kv heads on 16-way axis -> replicated
    spec = logical_to_spec(("batch", "seq", "kv_heads", "head_dim"),
                           (16, 4096, 8, 128), r)
    assert spec[2] is None
    # divisible kv heads shard
    spec = logical_to_spec(("batch", "seq", "kv_heads", "head_dim"),
                           (16, 4096, 32, 128), r)
    assert spec[2] in ("model", ("model",))


def test_batch_partial_axis_products(mesh):
    r = _rules(mesh)
    # batch rule is ("pod", "data"); no pod axis on this mesh -> data only
    spec = logical_to_spec(("batch", "seq"), (256, 4096), r)
    assert spec[0] in ("data", ("data",))
    # batch=1 (long_500k): replicated
    spec = logical_to_spec(("batch", "seq"), (1, 4096), r)
    assert spec[0] is None


def test_vocab_padding_requirement(mesh):
    r = _rules(mesh)
    # unpadded mamba2 vocab is indivisible -> replicate; padded shards
    assert logical_to_spec(("vocab",), (50280,), r)[0] is None
    assert logical_to_spec(("vocab",), (50432,), r)[0] in ("model", ("model",))


def test_tree_shardings_walks_pairs(mesh):
    axes = {"w": ("embed_fsdp", "ffn"), "scale": ("embed",)}
    shapes = {"w": jax.ShapeDtypeStruct((256, 512), jax.numpy.float32),
              "scale": jax.ShapeDtypeStruct((256,), jax.numpy.float32)}
    sh = tree_shardings(axes, shapes, mesh)
    assert sh["w"].spec in (P("data", "model"), P(("data",), ("model",)))
    assert sh["scale"].spec == P(None)


def test_spec_bytes(mesh):
    sds = jax.ShapeDtypeStruct((256, 512), jax.numpy.float32)
    assert spec_bytes(sds, P(("data",), ("model",)), mesh) == (256 // 16) * (512 // 16) * 4
    assert spec_bytes(sds, P(None, None), mesh) == 256 * 512 * 4
