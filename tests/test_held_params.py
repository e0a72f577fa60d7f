"""``ops/layers.py:HeldParams``: ``param`` of a parameter that exists reads
it and compares its shape directly; everything else is flax's, the errors
too.  (That every family's serving step declares its parameters through
it: tests/test_flat_step.py.)"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import errors
from flax import linen as nn
from flax.core import scope as scope_lib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from easyparallellibrary_tpu.ops.layers import HeldParams  # noqa: E402


class Plain(nn.Module):
  """Two parameters, one of them boxed, declared flax's way."""
  width: int = 3
  twice: bool = False

  @nn.compact
  def __call__(self, x):
    w = self.param("w", nn.with_partitioning(
        nn.initializers.normal(1.0), (None, None)), (x.shape[-1], self.width))
    b = self.param("b", nn.initializers.ones_init(), (self.width,),
                   jnp.float32)
    if self.twice:
      self.param("b", nn.initializers.ones_init(), (self.width,))
    return x @ w + b


class Held(HeldParams, Plain):
  pass


class Unshaped(HeldParams, nn.Module):
  """An initializer that is handed no shape."""

  @nn.compact
  def __call__(self, x):
    return x * self.param("s", lambda key: jnp.full((2,), 3.0))


@pytest.fixture
def flax_params(monkeypatch):
  """Names of the parameters flax's own ``Scope.param`` was asked for."""
  seen, real = [], scope_lib.Scope.param

  def param(self, name, *args, **kwargs):
    seen.append(name)
    return real(self, name, *args, **kwargs)
  monkeypatch.setattr(scope_lib.Scope, "param", param)
  return seen


X = jnp.ones((4, 2))


def test_init_goes_flaxs_way_and_gives_the_same_tree(flax_params):
  key = jax.random.PRNGKey(3)
  held, plain = Held().init(key, X), Plain().init(key, X)
  assert flax_params == ["w", "b", "w", "b"]
  assert jax.tree_util.tree_structure(held) == jax.tree_util.tree_structure(
      plain)
  for a, b in zip(jax.tree_util.tree_leaves(held),
                  jax.tree_util.tree_leaves(plain)):
    np.testing.assert_array_equal(a, b)
  assert isinstance(held["params"]["w"], nn.Partitioned)


def test_a_parameter_that_exists_is_read_with_no_abstract_evaluation(
    flax_params):
  variables = Plain().init(jax.random.PRNGKey(3), X)
  del flax_params[:]
  want = Plain().apply(variables, X)
  assert flax_params == ["w", "b"]
  del flax_params[:]
  got = Held().apply(variables, X)
  assert flax_params == []
  np.testing.assert_array_equal(got, want)
  # the same program, to the byte
  lowered = lambda m: jax.jit(m.apply).lower(variables, X).as_text()
  assert lowered(Held()) == lowered(Plain())


def test_a_shape_that_differs_is_flaxs_error():
  variables = Plain().init(jax.random.PRNGKey(3), X)
  for module in (Plain(width=5), Held(width=5)):
    with pytest.raises(errors.ScopeParamShapeError) as err:
      module.apply(variables, X)
    assert "(2, 3)" in str(err.value) and "(2, 5)" in str(err.value)


def test_a_name_declared_twice_is_flaxs_error():
  variables = Plain().init(jax.random.PRNGKey(3), X)
  for module in (Plain(twice=True), Held(twice=True)):
    with pytest.raises(errors.NameInUseError):
      module.apply(variables, X)


def test_an_initializer_that_is_handed_no_shape_goes_flaxs_way(flax_params):
  variables = Unshaped().init(jax.random.PRNGKey(0), jnp.ones((2,)))
  del flax_params[:]
  np.testing.assert_array_equal(Unshaped().apply(variables, jnp.ones((2,))),
                                [3.0, 3.0])
  assert flax_params == ["s"]
