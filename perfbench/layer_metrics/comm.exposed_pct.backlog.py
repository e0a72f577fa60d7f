"""Share of the traced window in which a collective runs on a chip and no
compute does, averaged over the chips, where it moves
``serve_tokens_per_s``.

``comm.exposed_pct``'s arithmetic (``harness/xplane.py``: collectives by
XLA's own instruction names, ``all-reduce``, ``all-gather``, ...) PLUS the
collectives a ``shard_map`` body issues itself, whose instructions carry
the primitive's name on the device trace (``all_to_all.<n>``,
``all_gather.<n>``, ``psum.<n>``, ...) and which that pattern does not
match.  Those are synchronous operations on the ``XLA Ops`` line, where one
operation runs at a time: all of their time is exposed (it includes a
chip's wait for the slowest of its peers).  ``None`` on one chip or where
no collective of either spelling ran."""

import re

OWN = re.compile(r"^(all_to_all|all_gather|all_reduce|reduce_scatter|psum|"
                 r"pmax|pmin|ppermute|collective_permute)")


def read(ctx):
  block = ctx.get("trace")
  if not block or ctx.get("chips", 1) < 2:
    return None
  own = sum(t for name, t in block.get("op_seconds", {}).items()
            if OWN.match(name))
  exposed = block["exposed_collective_s"] + own
  if block["collective_s"] <= 0 and own <= 0:
    return None
  return 100.0 * exposed / block["window_s"]
