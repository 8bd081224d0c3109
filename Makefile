# dnet-tpu developer targets.  Tier-1 is the pytest command ROADMAP.md
# pins; the dnetlint targets wrap scripts/dnetlint.py (full run for CI,
# diff run for the pre-commit hot path — lints only files changed vs
# HEAD and exits non-zero on any new finding, in seconds not minutes).

PY ?= python

.PHONY: tier1 dnetlint dnetlint-diff dnetlint-report bench-compare chaos chaos-smoke

tier1:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# regression diff of two load-test records (dnet_tpu/loadgen/compare.py):
#   make bench-compare OLD=<old>.json NEW=<new>.json \
#        FAIL_ON='--fail-on goodput.tok_s=-5%'
# the events sanity leg runs first: the wide-event vocabulary must agree
# with the dnet_events_total exposition (metrics pass 15) before bench
# numbers are compared — a drifted vocabulary invalidates event-based
# postmortems of either record
bench-compare:
	JAX_PLATFORMS=cpu $(PY) scripts/check_metrics_names.py
	$(PY) scripts/bench_compare.py $(OLD) $(NEW) $(FAIL_ON)

# chaos campaigns (scripts/chaos_campaign.py): the smoke slice is <= 8
# cells over the fast scenarios and exits 1 on any invariant violation —
# tier-1-friendly; `make chaos` runs the full (point x kind x scenario)
# matrix plus the composed failover+resume cell and writes
# CHAOS_r$(ROUND).json (slow: membership storms, two fleets of rings).
# SEED pins the entire cell schedule and every repro string.
SEED ?= 0
ROUND ?= 1
chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/chaos_campaign.py --smoke \
		--seed $(SEED) --out CHAOS_smoke.json

chaos:
	JAX_PLATFORMS=cpu $(PY) scripts/chaos_campaign.py \
		--seed $(SEED) --round $(ROUND) $(if $(MODEL),--model $(MODEL))

dnetlint:
	$(PY) scripts/dnetlint.py

# pre-commit shape: `make dnetlint-diff` (or with REV=main) — AST-only,
# changed files only, cross-file context still loaded so results agree
# with the full run for those files
REV ?= HEAD
dnetlint-diff:
	$(PY) scripts/dnetlint.py --diff $(REV)

dnetlint-report:
	$(PY) scripts/dnetlint.py --json
