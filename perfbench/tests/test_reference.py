"""The plain reference against the package's model at toy width, float32
on both sides: forward logits, loss, and one AdamW step against optax."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from perfbench.reference import gpt2
from perfbench.runners import epl_gpt

CFG = gpt2.GPT2Config(n_layer=2, n_embd=64, n_head=4, n_inner=256,
                      n_positions=32, vocab_size=128,
                      layer_norm_epsilon=1e-6)
MODEL = {"dtype": "float32", "param_dtype": "float32", "remat": False,
         "attn_impl": "xla", "remat_policy": "nothing", "loss_chunk": 0}


def _both():
  import easyparallellibrary_tpu as epl
  from easyparallellibrary_tpu.models import GPT
  epl.init()
  model = GPT(epl_gpt.gpt_config(CFG, MODEL))
  ref = gpt2.init_params(CFG, gpt2.seed_key(2 ** 31 + 3))
  shell = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
  return model, ref, epl_gpt.to_program_tree(ref, shell["params"])


def test_seed_key_takes_large_seeds_and_differs():
  a = jax.random.key_data(gpt2.seed_key(2 ** 31 + 3))
  b = jax.random.key_data(gpt2.seed_key(2 ** 31 + 4))
  assert not np.array_equal(a, b)
  assert np.array_equal(a, jax.random.key_data(gpt2.seed_key(2 ** 31 + 3)))


def test_forward_and_loss_agree_with_the_package():
  from easyparallellibrary_tpu.models.gpt import gpt_loss
  model, ref, prog = _both()
  ids = jax.random.randint(jax.random.PRNGKey(1), (3, 33), 0, 128)
  with jax.default_matmul_precision("highest"):
    got = model.apply({"params": prog}, ids[:, :-1])
    got_loss = gpt_loss(model, prog, {"ids": ids})[0]
  want = gpt2.logits(CFG, ref, ids[:, :-1])
  assert float(jnp.abs(got - want).max()) < 2e-5
  want_loss = gpt2.loss(CFG, ref, ids) / (3 * 32)
  assert abs(float(got_loss) - float(want_loss)) < 1e-5
  # every program leaf has a reference name, and the names are unique
  names = [epl_gpt.ref_name(p) for p, _ in
           jax.tree_util.tree_leaves_with_path(prog)]
  assert len(set(names)) == len(names) == 4 + 10 * CFG.n_layer


def test_adamw_matches_optax():
  _, ref, _ = _both()
  ids = jax.random.randint(jax.random.PRNGKey(2), (4, 33), 0, 128)
  opt = {"learning_rate": 3e-4, "weight_decay": 0.01, "b1": 0.9,
         "b2": 0.999, "eps": 1e-8}
  _, g = gpt2.loss_and_grads(CFG, ref, ids, row_block=2)
  _, g_one = gpt2.loss_and_grads(CFG, ref, ids, row_block=4)
  for a, b in zip(jax.tree_util.tree_leaves(g),
                  jax.tree_util.tree_leaves(g_one)):
    assert float(jnp.abs(a - b).max()) < 1e-6      # row blocks add up
  tx = optax.adamw(3e-4, weight_decay=0.01)
  st = tx.init(ref)
  params, m, v = ref, jax.tree_util.tree_map(jnp.zeros_like, ref), \
      jax.tree_util.tree_map(jnp.zeros_like, ref)
  want = ref
  for t in (1, 2):
    up, st = tx.update(g, st, want)
    want = optax.apply_updates(want, up)
    params, m, v = gpt2.adamw_update(params, g, m, v, float(t), 3e-4,
                                     weight_decay=0.01)
  for a, b in zip(jax.tree_util.tree_leaves(params),
                  jax.tree_util.tree_leaves(want)):
    assert float(jnp.abs(a - b).max()) < 1e-6


def test_lower_precisions_differ_in_the_expected_order():
  _, ref, _ = _both()
  ids = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, 128)
  full = gpt2.logits(CFG, ref, ids)
  err = {p: float(jnp.abs(gpt2.logits(CFG, ref, ids, p) - full).max())
         for p in ("bfloat16", "int8")}
  assert 0 < err["bfloat16"] < err["int8"]
