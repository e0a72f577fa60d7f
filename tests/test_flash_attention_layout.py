"""Which layout the flash kernels take a call's operands in
(kernels/flash_attention.py:flash_layout, PR 49): one rule on shapes, asked
by both entries, recorded in the trace; what it declines runs today's
head-major program, and ring attention's primitives never ask."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.kernels import flash_attention
from easyparallellibrary_tpu.observability import trace as trace_lib

fa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.flash_attention")


@pytest.mark.parametrize("S,H,D,itemsize,want", [
    (1024, 20, 64, 2, "rows"),      # gpt2l-train-zero1-4chip, a chip
    (1024, 16, 64, 4, "rows"),
    (512, 12, 64, 4, "rows"),       # BERT-base
    (1024, 10, 128, 2, "rows of one qkv"),   # one head a tile: no padding
    (1024, 3, 128, 2, "rows of one qkv"),    # to save on a head array
    (1024, 8, 32, 2, "rows"),       # four a tile
    (1024, 19, 64, 2, "heads"),     # an odd head has no partner
    (1024, 6, 32, 2, "heads"),      # six are not whole fours
    (1024, 16, 80, 2, "heads"),     # 80 lanes are no share of a tile
    (1024, 4, 256, 2, "heads"),     # wider than a tile
    (1024, 16, 16, 2, "heads"),     # eight a tile: unrolled, past VMEM
    (16384, 16, 64, 2, "heads"),    # past the resident regime: streaming
    (8192, 16, 64, 4, "heads"),     # the same wall in float32
    (8192, 16, 64, 2, "rows"),      # where the resident regime ends
], ids=lambda x: str(x))
def test_the_rule_reads_shapes_alone(S, H, D, itemsize, want):
  """``rows of one qkv``: rows from the fused projection, where no head
  array exists; a ``[B, S, H, 128]`` array handed over stays head-major
  (relaid into rows it read +48% on the chip: PERF.md section 6, PR 49)."""
  from_arrays = "heads" if want == "rows of one qkv" else want
  fused = "rows" if want == "rows of one qkv" else want
  assert fa.flash_layout(S, H, D, itemsize) == from_arrays
  assert fa.flash_layout(S, H, D, itemsize, fused=True) == fused


def _pallas_calls(fn, *args):
  """``[(kernel name, operand shapes)]`` of every ``pallas_call`` equation
  in ``fn``'s jaxpr, inner jaxprs included."""
  found = []

  def walk(jaxpr):
    for eqn in jaxpr.eqns:
      if eqn.primitive.name == "pallas_call":
        found.append((eqn.params["name"],
                      [tuple(v.aval.shape) for v in eqn.invars]))
      for value in eqn.params.values():
        for inner in (value if isinstance(value, (list, tuple))
                      else (value,)):
          if hasattr(inner, "eqns"):
            walk(inner)
          elif hasattr(inner, "jaxpr") and hasattr(inner.jaxpr, "eqns"):
            walk(inner.jaxpr)

  walk(jax.make_jaxpr(fn)(*args).jaxpr)
  return found


def _loss(attend):
  return lambda *xs: jnp.sum(attend(*xs).astype(jnp.float32) ** 2)


@pytest.mark.parametrize("shape,why", [
    ((2, 128, 3, 64), "odd H"),
    ((2, 128, 4, 80), "D 80"),
    ((1, 128, 8, 16), "D 16"),
], ids=lambda x: x if isinstance(x, str) else "x".join(map(str, x)))
def test_what_the_rule_declines_runs_todays_head_major_program(shape, why):
  """The fallback IS the program of before: the same jaxpr, equation for
  equation, as the head-major kernels behind explicit transposes, and its
  three calls see ``[B, H, S, D]``."""
  B, S, H, D = shape
  assert fa.flash_layout(S, H, D, 2) == "heads", why
  x = jnp.zeros(shape, jnp.bfloat16)
  tile = fa._default_block(S, d=D, itemsize=2)
  t = lambda a: a.transpose(0, 2, 1, 3)
  through_entry = jax.value_and_grad(
      _loss(lambda q, k, v: flash_attention(q, k, v, causal=True)),
      (0, 1, 2))
  by_hand = jax.value_and_grad(
      _loss(lambda q, k, v: t(fa._flash(t(q), t(k), t(v), True, tile,
                                        tile))), (0, 1, 2))
  assert (str(jax.make_jaxpr(through_entry)(x, x, x))
          == str(jax.make_jaxpr(by_hand)(x, x, x)))
  calls = _pallas_calls(through_entry, x, x, x)
  assert [name for name, _ in calls] == ["flash_fwd", "flash_dkv",
                                        "flash_dq"]
  for _, shapes in calls:
    assert shapes[:3] == [(B, H, S, D)] * 3


def test_a_head_beyond_the_resident_regime_streams_head_major(monkeypatch):
  monkeypatch.setattr(fa, "_RESIDENT_MAX_BYTES", 0)
  assert fa.flash_layout(256, 2, 64, 2) == "heads"
  x = jnp.zeros((1, 256, 2, 64), jnp.bfloat16)
  calls = _pallas_calls(
      jax.value_and_grad(_loss(lambda q, k, v: flash_attention(
          q, k, v, causal=True)), (0, 1, 2)), x, x, x)
  assert len(calls) == 3 and all(s[0] == (1, 2, 256, 64) for _, s in calls)


def test_where_the_rule_says_rows_no_call_sees_a_head_major_operand():
  """The entries' operands at a shape laid out in rows: ``[B, S, H x D]``
  from ``flash_attention``, the ONE ``[B, S, 3 x H x D]`` three times from
  ``flash_attention_qkv``; lse and delta stay ``[B, H, 8, S]``."""
  B, S, H, D = 2, 256, 4, 64
  x = jnp.zeros((B, S, H, D), jnp.bfloat16)
  calls = _pallas_calls(
      jax.value_and_grad(_loss(lambda q, k, v: flash_attention(
          q, k, v, causal=True)), (0, 1, 2)), x, x, x)
  assert [name for name, _ in calls] == ["flash_fwd", "flash_dkv",
                                        "flash_dq"]
  assert calls[0][1] == [(B, S, H * D)] * 3
  for _, shapes in calls[1:]:
    assert shapes == [(B, S, H * D)] * 4 + [(B, H, 8, S)] * 2
  qkv = jnp.zeros((B, S, 3 * H * D), jnp.bfloat16)
  calls = _pallas_calls(jax.value_and_grad(_loss(
      lambda qkv: fa.flash_attention_qkv(qkv, H, causal=True))), qkv)
  assert calls[0][1] == [(B, S, 3 * H * D)] * 3
  for _, shapes in calls[1:]:
    assert shapes == ([(B, S, 3 * H * D)] * 3 + [(B, S, H * D)]
                      + [(B, H, 8, S)] * 2)


@pytest.mark.parametrize("H,layout,operand", [
    (4, "rows", (2, 128, 128)),       # two heads a chip: one pair
    (2, "heads", (2, 1, 128, 64)),    # one head a chip: no pair
])
def test_on_a_mesh_the_rule_reads_the_heads_a_chip_holds(H, layout,
                                                         operand):
  """``data:4,model:2``: the heads are divided over ``model`` and the
  kernels run a chip's share under ``shard_map``."""
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  B, S, D = 8, 128, 64
  r = np.random.RandomState(0)
  sharding = NamedSharding(mesh, P("data", None, "model", None))
  q, k, v = (jax.device_put(jnp.asarray(r.randn(B, S, H, D), jnp.float32),
                            sharding) for _ in range(3))
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    attend = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    calls = _pallas_calls(attend, q, k, v)
    out = attend(q, k, v)
    facts = [e["args"] for e in tracer.events()
             if e["name"] == "train/flash_layout"]
  finally:
    trace_lib.reset()
  assert facts == [{"layout": layout}]
  assert calls[0][1] == [operand] * 3
  s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
  s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
  want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
  np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)


def test_heads_divided_over_chips_are_cut_as_the_models_cut_them():
  """``flash_attention_qkv`` on ``model:2``: a chip holds half the heads,
  so the fused array is not one chip's q | k | v; the three are cut from
  the ``[B, S, 3, H, D]`` view and go through ``flash_attention``."""
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  epl.Env.get().cluster.build_mesh()
  B, S, H, D = 4, 128, 4, 64
  qkv = jnp.asarray(np.random.RandomState(1).randn(B, S, 3 * H * D),
                    jnp.float32)
  calls = _pallas_calls(
      lambda qkv: fa.flash_attention_qkv(qkv, H, causal=False), qkv)
  assert calls[0][1] == [(1, S, 2 * D)] * 3
  got = jax.jit(lambda qkv: fa.flash_attention_qkv(qkv, H, causal=False))(
      qkv)
  q, k, v = (qkv.reshape(B, S, 3, H, D)[:, :, i] for i in range(3))
  s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
  want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
  np.testing.assert_allclose(got, want.reshape(B, S, H * D), rtol=2e-5,
                             atol=2e-6)


@pytest.mark.parametrize("shape,layout", [((1, 128, 2, 64), "rows"),
                                          ((1, 128, 3, 64), "heads")])
def test_a_traced_run_records_the_layout_once_a_compile(shape, layout):
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True, ring_capacity=4))
  try:
    x = jnp.ones(shape, jnp.float32)
    attend = jax.jit(lambda x: flash_attention(x, x, x, causal=True))
    for _ in range(3):
      attend(x)
    tracer.clear()
    facts = [e for e in tracer.events()
             if e["name"] == "train/flash_layout"]
  finally:
    trace_lib.reset()
  assert facts == [{"ph": "M", "name": "train/flash_layout", "pid": 0,
                    "tid": 0, "args": {"layout": layout}}]
  # and nothing at all where no tracer is on
  flash_attention(x, x, x, causal=True)
  assert trace_lib.get_tracer().events() == [
      e for e in trace_lib.get_tracer().events()
      if e["name"] != "train/flash_layout"]


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attentions_primitives_stay_head_major(causal):
  """``_fwd`` / ``_bwd_kernels`` are called by ring attention on
  ``[B, H, S, D]`` with a key length of their own; they ask no rule, even
  at a shape the entries would lay out in rows."""
  B, H, S, D = 1, 2, 128, 64
  assert fa.flash_layout(S, H, D, 4) == "rows"
  q, do = (jnp.ones((B, H, S, D), jnp.float32) for _ in range(2))
  k = v = jnp.ones((B, H, 2 * S, D), jnp.float32)
  l8 = jnp.zeros((B, H, 8, S), jnp.float32)
  calls = _pallas_calls(lambda q, k, v: fa._fwd(q, k, v, causal, 64, 64),
                        q, k, v)
  assert calls == [("flash_fwd", [(B, H, S, D), (B, H, 2 * S, D),
                                  (B, H, 2 * S, D)])]
  calls = _pallas_calls(
      lambda q, k, v, do: fa._bwd_kernels(q, k, v, do, l8, l8, causal, 64,
                                          64), q, k, v, do)
  assert [name for name, _ in calls] == ["flash_dkv", "flash_dq"]
  assert all(s[0] == (B, H, S, D) and s[1] == (B, H, 2 * S, D)
             for _, s in calls)


def test_flash_attention_lse_stays_head_major():
  from easyparallellibrary_tpu.kernels.flash_attention import (
      flash_attention_lse)
  x = jnp.ones((1, 128, 2, 64), jnp.float32)
  calls = _pallas_calls(lambda x: flash_attention_lse(x, x, x), x)
  assert calls == [("flash_fwd", [(1, 2, 128, 64)] * 3)]


def test_berts_bidirectional_forward_is_what_it_was_within_rounding():
  """``models/bert.py`` hands its fused projection over whole; at 2 heads
  of 64 the rule says rows.  Against the dense ``xla`` lowering on the same
  parameters, as before."""
  from easyparallellibrary_tpu.models import Bert, BertConfig
  epl.init()
  base = dict(vocab_size=256, num_layers=2, num_heads=2, d_model=128,
              d_ff=256, max_seq_len=128, dtype=jnp.float32)
  flash = Bert(BertConfig(**base, attn_impl="pallas_flash"))
  xla = Bert(BertConfig(**base, attn_impl="xla"))
  ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 128)),
                    jnp.int32)
  params = flash.init(jax.random.PRNGKey(0), ids)["params"]
  calls = _pallas_calls(lambda p: flash.apply({"params": p}, ids), params)
  assert [s[0] for _, s in calls] == [(2, 128, 384)] * 2
  np.testing.assert_allclose(flash.apply({"params": params}, ids),
                             xla.apply({"params": params}, ids),
                             rtol=2e-4, atol=2e-5)


def test_gpts_train_step_hands_the_projection_over_whole():
  """``models/gpt.py`` with ``pallas_flash``: loss and gradients equal the
  dense lowering's, through remat with ``dots_flash``, and every flash
  call of the step reads the fused ``[B, S, 3 x D]``."""
  from easyparallellibrary_tpu.models import GPT, GPTConfig
  from easyparallellibrary_tpu.models.gpt import gpt_loss
  epl.init()
  base = dict(vocab_size=128, num_layers=2, num_heads=2, d_model=128,
              d_ff=256, max_seq_len=128, dtype=jnp.float32)
  flash = GPT(GPTConfig(**base, attn_impl="pallas_flash", remat=True,
                        remat_policy="dots_flash"))
  xla = GPT(GPTConfig(**base, attn_impl="xla"))
  ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 129)),
                    jnp.int32)
  params = flash.init(jax.random.PRNGKey(0), ids[:, :-1])["params"]

  def loss_of(model):
    return lambda p: gpt_loss(model, p, {"ids": ids})[0]

  got = jax.value_and_grad(loss_of(flash))(params)
  want = jax.value_and_grad(loss_of(xla))(params)
  np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
  for g, r in zip(jax.tree_util.tree_leaves(got[1]),
                  jax.tree_util.tree_leaves(want[1])):
    np.testing.assert_allclose(g, r, rtol=2e-3, atol=2e-5)
  calls = _pallas_calls(jax.grad(loss_of(flash)), params)
  assert sorted(name for name, _ in calls) == (
      ["flash_dkv"] * 2 + ["flash_dq"] * 2 + ["flash_fwd"] * 2)
  assert all(s[0] == (2, 128, 384) for _, s in calls)
