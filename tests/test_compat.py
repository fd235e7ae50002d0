"""The small JAX helpers in ``repro.compat``, and the compiler-parameter
contract the Pallas kernels rely on."""
import jax
import jax.numpy as jnp
import pytest

from repro import compat


def test_tree_flatten_with_path_and_path_str():
    tree = {"outer": {"inner": jnp.ones(2)}, "leaf": jnp.zeros(3),
            "seq": [jnp.ones(1), jnp.ones(4)]}
    leaves, treedef = jax.tree.flatten_with_path(tree)
    names = {compat.path_str(path) for path, _ in leaves}
    assert names == {"outer/inner", "leaf", "seq/0", "seq/1"}
    rebuilt = jax.tree_util.tree_unflatten(treedef,
                                           [leaf for _, leaf in leaves])
    assert jax.tree.structure(rebuilt) == jax.tree.structure(tree)


def test_tpu_compiler_params_rejects_unknown_fields():
    """Kernels build ``pltpu.CompilerParams`` directly: a misspelled field
    is an error, never silently dropped."""
    from jax.experimental.pallas import tpu as pltpu
    params = pltpu.CompilerParams(dimension_semantics=("parallel",))
    assert tuple(params.dimension_semantics) == ("parallel",)
    with pytest.raises(TypeError):
        pltpu.CompilerParams(dimension_semantics=("parallel",),
                             some_flag_from_the_future=True)


def test_make_mesh_and_set_mesh():
    n = len(jax.devices())
    mesh = compat.make_mesh((n, 1), ("data", "model"))
    assert mesh.shape["data"] == n
    assert mesh.shape["model"] == 1
    with jax.set_mesh(mesh):
        pass


def test_auto_axis_types_shape():
    mesh = compat.make_mesh((1, 1, 1), ("a", "b", "c"))
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 3


def test_donation_kwargs_only_where_implemented():
    kw = compat.donation_kwargs(0, 2)
    if jax.default_backend() == "cpu":
        assert kw == {}
    else:
        assert kw == {"donate_argnums": (0, 2)}
