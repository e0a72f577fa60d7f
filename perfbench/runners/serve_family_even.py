"""Runner ``serve_family_even``: ``runners/serve_family.py`` on a backlog
that is taken in an EVEN order.

``harness/traffic.py`` gives every seed the same set of lengths and lets
the seed permute them freely.  That is the same work for every seed only
where a run consumes most of the population.  A cell whose requests live
as long as its window consumes a few dozen of them: 40 s of a 32-slot
engine on prompts of 4096-12288 and outputs of 128-512 finish ~45 requests
of 1024, a free permutation's first hundred differ threefold in prompt and
fourfold in output from seed to seed, and the tokens of the window follow
the share of slot-steps that decode: 8-10% quartile spread over sets of six
seeds, at step periods 1.5% apart (PERF.md section 6, PR 39).

Here the seed still only permutes the same quantiles, but within a
scrambled low-discrepancy order (``even_order``): every aligned stretch of
``2**j`` consecutive requests holds ONE prompt length from each of ``2**j``
equal slices of the distribution, and likewise one output length, for
every ``j``.  So whichever stretch of the sequence a window sees, it is
offered the same work; which length of a slice comes when, which prompt
meets which output, and the token ids stay the seed's.  Marginals,
population and every other parameter of the mix are untouched.

Everything else is ``serve_family``'s: that module reads its generator
through the global ``traffic_lib``, which ``run`` swaps for the length of
the call (the generator's own file is not this PR's to edit; ROADMAP R1
asks that it take the order as a parameter of the mix).
"""

from __future__ import annotations

import types

import numpy as np

from perfbench.harness import traffic as traffic_lib
from perfbench.runners import serve_family


def even_order(n: int, rng: np.random.Generator) -> np.ndarray:
  """A seeded permutation of ``range(n)``: the radical inverse of the
  request's number under Owen's nested scrambling (one seeded flip a node
  of the binary tree of slices), entries of ``n`` and above left out.  For
  ``n`` a power of two every aligned run of ``2**j`` consecutive entries
  holds exactly one index from each of ``2**j`` equal slices of
  ``range(n)``."""
  bits = max(1, (n - 1).bit_length())
  k = np.arange(1 << bits)
  inv = np.zeros_like(k)
  for b in range(bits):
    inv |= ((k >> b) & 1) << (bits - 1 - b)
  out = np.zeros_like(k)
  for level in range(bits):
    shift = bits - 1 - level
    flips = rng.integers(0, 2, size=1 << level)
    out |= (((inv >> shift) & 1) ^ flips[inv >> (shift + 1)]) << shift
  return out[out < n]


def backlog(mix: dict, seed: int, vocab: int):
  """``traffic.backlog`` with the two length sequences in ``even_order``
  (two scramblings: which prompt meets which output is the seed's)."""
  n = mix["population"]
  rng = np.random.default_rng([int(seed), 1])
  prompts = traffic_lib.length_quantiles(mix["prompt_len"], n)[
      even_order(n, rng)]
  outputs = traffic_lib.length_quantiles(mix["output_len"], n)[
      even_order(n, rng)]
  cap = mix.get("max_total_len")
  cdf = traffic_lib.token_cdf(mix.get("token_law", {"dist": "uniform"}),
                              vocab)
  reqs = []
  for i, (p, o) in enumerate(zip(prompts.tolist(), outputs.tolist())):
    if cap is not None and p + o > cap:
      o = max(1, cap - p)
    reqs.append(traffic_lib.Req(
        uid=i, due_s=0.0, prompt=traffic_lib.draw_tokens(rng, cdf, vocab, (p,)),
        max_new_tokens=o))
  return reqs


_EVEN = types.SimpleNamespace(Req=traffic_lib.Req,
                              open_loop=traffic_lib.open_loop,
                              backlog=backlog)


def run(**kw):
  theirs = serve_family.traffic_lib
  serve_family.traffic_lib = _EVEN
  try:
    return serve_family.run(**kw)
  finally:
    serve_family.traffic_lib = theirs
