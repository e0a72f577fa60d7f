# Build / test entry points (reference analog: /root/reference/Makefile).

all: build

build:
	$(MAKE) -C csrc

test: build
	python -m pytest tests/ -x -q

# epl-lint: static invariant checker (compile-once, host-sync,
# donation, metric schema, span pairing, lock discipline) over the
# package — exits non-zero on any non-baselined finding
# (docs/static_analysis.md; the quick-marked tests/test_analysis.py
# zero-findings test enforces the same gate in tier-1).
lint:
	python -m easyparallellibrary_tpu.analysis

# Perf regression gate: device cost-card invariants (compile count,
# flops/token, KV bytes/request, peak-HBM bound, donation-verified —
# collected live from the canonical tiny twins), pinned with tolerances
# in perf_budget.json (observability/perfgate.py; docs/observability.md
# "Device truth").  Counts, not speed: speed is perfbench/'s
# (BENCHMARK.json, PERF.md).  Regenerate the budget only for an
# intentional change:
# python -m easyparallellibrary_tpu.observability.perfgate --write-budget
perf-gate:
	python -m easyparallellibrary_tpu.observability.perfgate

# The full static + perf gate chain: epl-lint, then the perf budget.
gate: lint perf-gate

# Fault-injection suite standalone (testing/chaos.py + docs/robustness.md).
chaos:
	python -m pytest tests/test_resilience.py -q

# Serving chaos: NaN steps, hung steps, flaky drafters, Poisson overload
# against the resilient engine (docs/robustness.md "Serving resilience").
chaos-serve:
	python -m pytest tests/test_serving_resilience.py -q

# Router chaos: replica kills mid-decode, replica hangs, flapping health
# against the multi-replica control plane — bit-exact failover, graceful
# drain/rejoin, circuit breaker (docs/serving.md "Multi-replica serving")
# — ACROSS BOTH TRANSPORTS: the in-process simulations
# (test_serving_router.py) and the process-isolated real fault domain
# (test_serving_transport.py: SIGKILL/SIGSTOP/lost replies) — plus the
# fleet observability acceptance (one connected flow per migrated
# request, SLO breach window logged, diagnostic bundle captured;
# docs/observability.md "Reading a failover trace").
chaos-router:
	python -m pytest tests/test_serving_router.py tests/test_serving_transport.py tests/test_observability_fleet.py -q

# Process-transport chaos standalone: subprocess replicas behind the
# wire (serving/transport.py) — real os.kill(pid, SIGKILL) mid-decode
# with journal recovery, SIGSTOP stalls tripping wire deadlines into
# condemn+fence, dropped-reply exactly-once (uid dedup + watermark
# resync), breaker-probe child respawn, and orphan reaping
# (docs/robustness.md "Process-isolated replicas").
chaos-proc:
	python -m pytest tests/test_serving_transport.py -q

# Self-healing chaos: an injected 3x overload burst on a 2-replica
# process-transport fleet — the autoscaler spawns a third replica (a
# REAL subprocess), the autotuner tightens budgets, SLO burn recovers
# with no operator input, every non-shed request bit-exact vs the
# fault-free oracle, all replica compile counts stay 1, and after
# recovery the fleet drains back to 2 replicas; plus the quick-marked
# fault-free-equivalence pin (actuators enabled + no breaches ==
# baseline stream, zero actuations) (serving/autotune.py,
# serving/autoscale.py; docs/robustness.md "Self-healing fleet").
chaos-heal:
	python -m pytest tests/test_serving_autoscale.py -q

# Blue/green rollout chaos: begin a checkpoint rollout mid-traffic on a
# process-transport fleet, SIGKILL one blue replica child during the
# canary — its journaled requests fail over to the SURVIVING BLUE only
# (cross-version replay is refused; complete-in-place migration), zero
# requests lost, every response attributable to exactly one checkpoint
# version, the survivor's compile count stays 1, and the rollout still
# completes; plus the quick-marked contract pins (full rollout under
# live traffic, canary-breach rollback blue-bit-exact, fault-free
# guard) (serving/rollout.py; docs/robustness.md "Blue/green rollout").
chaos-rollout:
	python -m pytest tests/test_serving_rollout.py -q

# Front-door chaos: the streaming HTTP/SSE surface behind the reactor
# driver (serving/frontdoor/, serving/reactor.py) — reactor-vs-sweep
# bit-exactness pins, SSE byte-assembly vs direct submit(), real
# SIGKILL/SIGSTOP of process replicas behind live HTTP clients (zero
# lost, zero double-served), cancel-on-disconnect (slot + blocks freed,
# flow finalized), slow-reader shed isolation (docs/serving.md
# "Front door").
chaos-frontdoor:
	python -m pytest tests/test_serving_frontdoor.py -q

# Re-record the golden chaos-heal episode from a REAL 2-replica fleet
# (only when a policy change legitimately changes the actuation story;
# the golden-file diff then documents it ->
# tests/golden/sim_chaos_heal.json).
sim-golden:
	python tests/golden/record_sim_chaos_heal.py

help:
	@echo "Targets:"
	@echo "  build          - build the native IO extension (csrc/)"
	@echo "  test           - full pytest suite (stops on first failure)"
	@echo "  lint           - epl-lint static invariant checker (zero findings gate)"
	@echo "  perf-gate      - cost-card gate: counts of the compiled twins (perf_budget.json)"
	@echo "  gate           - lint + perf-gate"
	@echo "  chaos          - training fault-injection suite"
	@echo "  chaos-serve    - serving resilience chaos (NaN/hang/overload)"
	@echo "  chaos-router   - fleet chaos: replica kills, hangs, flapping health (both transports)"
	@echo "  chaos-proc     - process-transport chaos: SIGKILL/SIGSTOP/lost replies/orphans"
	@echo "  chaos-heal     - self-healing fleet: overload burst -> autotune + autoscale -> recover"
	@echo "  chaos-rollout  - blue/green rollout chaos: SIGKILL a blue mid-canary, zero lost"
	@echo "  chaos-frontdoor - HTTP/SSE front door chaos: disconnects, slow readers, kills behind the reactor"
	@echo "  sim-golden     - re-record the golden chaos-heal episode (real fleet)"
	@echo "  clean          - clean native build artifacts"
	@echo "Live watching: python -m easyparallellibrary_tpu.observability.report --follow <metrics.jsonl>"

clean:
	$(MAKE) -C csrc clean

.PHONY: all build test lint perf-gate gate chaos chaos-serve chaos-router chaos-proc chaos-heal chaos-rollout chaos-frontdoor sim-golden help clean
