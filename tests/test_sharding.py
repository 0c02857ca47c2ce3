"""Sharding rules: divisibility fitting + spec structure (host-side)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config, get_reduced
from repro.launch.inputs import (abstract_cache, abstract_params, config_for,
                                 input_specs, skip_reason)
from repro.launch.sharding import _fit, batch_pspecs, cache_pspecs, param_pspecs


class FakeMesh:
    shape = {"data": 16, "model": 16}
    axis_names = ("data", "model")


def test_fit_drops_nondivisible():
    m = FakeMesh()
    spec = _fit(P("model", "data"), (50280, 2560), m)
    assert spec == P(None, "data")
    spec = _fit(P(("data", "model"), None), (512, 7), m)
    assert spec == P(("data", "model"), None)


def test_fit_strict_raises_with_offending_path_and_axis():
    m = FakeMesh()
    with pytest.raises(ValueError, match=(
            r"'embed' dim 0 has size 50280.*'model'")):
        _fit(P("model", "data"), (50280, 2560), m, strict=True,
             path="embed")
    # strict on a fitting spec stays silent
    assert _fit(P("data", None), (512, 7), m, strict=True,
                path="embed") == P("data", None)


def test_fit_default_warns_once_per_site():
    import warnings as warnings_mod

    from repro.launch import sharding as shard_mod
    m = FakeMesh()
    shard_mod._FIT_WARNED.clear()
    with warnings_mod.catch_warnings(record=True) as rec:
        warnings_mod.simplefilter("always")
        _fit(P("model", None), (50280, 7), m, path="embed")
        _fit(P("model", None), (50280, 7), m, path="embed")  # same site
        _fit(P("model", None), (50280, 7), m, path="head")   # new site
    msgs = [str(w.message) for w in rec
            if issubclass(w.category, UserWarning)]
    assert len(msgs) == 2
    assert "replicating 'embed' dim 0" in msgs[0]
    assert "strict=True" in msgs[0]
    assert "replicating 'head' dim 0" in msgs[1]


def test_param_pspecs_strict_raises_on_misfit_tree():
    cfg = get_config("roberta-large")   # vocab 50265: not divisible by 16
    params = abstract_params(cfg)
    with pytest.raises(ValueError, match=r"embed"):
        param_pspecs(params, cfg, FakeMesh(), strict=True)
    # the default path still builds the full spec tree (replicating)
    specs = param_pspecs(params, cfg, FakeMesh())
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(params)


def test_param_pspecs_cover_tree():
    cfg = get_config("gemma-2b")
    params = abstract_params(cfg)
    specs = param_pspecs(params, cfg, FakeMesh())
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        assert len(spec) <= leaf.ndim
        # every sharded dim divides evenly
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = int(np.prod([FakeMesh.shape[a] for a in axes]))
            assert leaf.shape[dim] % size == 0


@pytest.mark.parametrize("arch", ["gemma_2b", "mamba2_2_7b", "olmoe_1b_7b",
                                  "whisper_small"])
@pytest.mark.parametrize("shape_name", ["train_4k", "decode_32k"])
def test_specs_build_for_all_kinds(arch, shape_name):
    shape = INPUT_SHAPES[shape_name]
    cfg, _ = config_for(arch, shape)
    if skip_reason(cfg, shape):
        pytest.skip("combination skipped by design")
    mesh = FakeMesh()
    params = abstract_params(cfg)
    param_pspecs(params, cfg, mesh)
    if shape.kind == "decode":
        cache = abstract_cache(cfg, shape)
        specs = cache_pspecs(cache, cfg, mesh, shape.global_batch)
        assert jax.tree.structure(
            specs, is_leaf=lambda x: isinstance(x, P)) \
            == jax.tree.structure(cache)
    else:
        batch = input_specs(cfg, shape)
        specs = batch_pspecs(batch, cfg, mesh, shape.global_batch)
        for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
            assert isinstance(s, P)


def test_long500k_variants():
    shape = INPUT_SHAPES["long_500k"]
    cfg, note = config_for("command_r_plus_104b", shape)
    assert cfg.sliding_window == 4096 and "sliding-window" in note
    cfg2, note2 = config_for("mamba2_2_7b", shape)
    assert cfg2.sliding_window is None and note2 == ""
    assert skip_reason(get_config_safe("whisper_small"), shape)
    assert skip_reason(get_config_safe("roberta_large"), shape)


def get_config_safe(name):
    return get_config(name)
