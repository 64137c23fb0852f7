"""The checkpoint file: a bit-exact round trip with its metadata, leaves
at page-aligned offsets viewed read-only from one mapping of the file,
truncated or foreign files refused, and a WeightStore whose publish
leaves the views of an earlier version as they were."""
import mmap

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.checkpoint import load_metadata, load_pytree, read_flat, save_pytree
from repro.checkpoint.io import ALIGN
from repro.serving import WeightStore


def _tree(dtype, seed=0):
    rng = np.random.default_rng(seed)

    def arr(*shape):
        return (rng.standard_normal(shape) * 100).astype(dtype)
    return {"embed": arr(7, 5),
            "layers": [{"w": arr(5, 3), "b": arr(3)}, (arr(2, 2), arr())],
            "scalar": arr(),
            "empty": arr(0, 4)}


def _mapping_of(arr):
    base = arr.base
    while base is not None and not isinstance(base, mmap.mmap):
        base = getattr(base, "base", getattr(base, "obj", None))
    return base


@pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, np.float32, np.int32])
def test_roundtrip_is_bit_exact_with_metadata(tmp_path, dtype):
    tree = _tree(dtype)
    path = str(tmp_path / "t.ckpt")
    save_pytree(path, tree, metadata={"step": 3, "version": 2})
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)
    loaded = load_pytree(path, like)
    assert jax.tree.structure(loaded) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(loaded)):
        b = np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    meta = load_metadata(path)
    assert meta["step"] == 3 and meta["version"] == 2
    assert meta["treedef"] == str(jax.tree.structure(tree))


def test_leaves_are_page_aligned_read_only_views_of_one_mapping(tmp_path):
    path = str(tmp_path / "t.ckpt")
    save_pytree(path, _tree(ml_dtypes.bfloat16))
    flat = read_flat(path)
    assert flat["layers/[0]/w"].dtype == ml_dtypes.bfloat16
    assert flat["scalar"].shape == () and flat["empty"].shape == (0, 4)
    mappings = set()
    for key, arr in flat.items():
        assert not arr.flags.owndata, key
        assert not arr.flags.writeable, key
        assert arr.__array_interface__["data"][0] % ALIGN == 0, key
        mapping = _mapping_of(arr)
        assert isinstance(mapping, mmap.mmap), key
        mappings.add(id(mapping))
    assert len(mappings) == 1


@pytest.mark.parametrize("keep", ["prefix", "header", "last_leaf"])
def test_truncated_file_raises(tmp_path, keep):
    path = tmp_path / "t.ckpt"
    save_pytree(str(path), _tree(np.float32))
    data = path.read_bytes()
    cut = {"prefix": 10, "header": 40, "last_leaf": len(data) - 1}[keep]
    path.write_bytes(data[:cut])
    with pytest.raises(ValueError, match="truncated"):
        read_flat(str(path))


def test_other_formats_are_refused(tmp_path):
    path = tmp_path / "t.npz"
    np.savez(path, w=np.ones(3, np.float32))
    with pytest.raises(ValueError, match="not a version"):
        read_flat(str(path))
    with pytest.raises(ValueError, match="not a version"):
        load_metadata(str(path))


def test_store_publish_leaves_earlier_views_unchanged(tmp_path):
    store = WeightStore(str(tmp_path))
    first = _tree(np.float32, seed=1)
    second = _tree(np.float32, seed=2)
    store.publish("m", first)
    old = read_flat(store._path("m"))
    assert store.publish("m", second) == 2
    assert store.version("m") == 2
    assert load_metadata(store._path("m"))["version"] == 2
    for key, arr in zip(old, jax.tree.leaves(first)):
        np.testing.assert_array_equal(old[key], arr)
    for a, b in zip(jax.tree.leaves(second), jax.tree.leaves(store.load("m"))):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_load_into_eval_shape_template_equals_params(tmp_path):
    from repro.configs import get_config
    from repro.models import make_model
    model = make_model(get_config("qwen2-0.5b").reduced(d_model=64))
    params = model.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "p.ckpt")
    save_pytree(path, params)
    loaded = load_pytree(path, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert bool(jnp.array_equal(a, b))
