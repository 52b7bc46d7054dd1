# Build + deploy entry points.  The reference ships one prebuilt image
# (nizepart/mlflow-operator:latest, README.md:32); this framework builds
# its three first-party images from source.

# bash, not sh: the verify recipe needs pipefail/PIPESTATUS (dash has
# neither and dies on `set -o pipefail`).
SHELL    := /bin/bash

PKG      := research_and_development_of_kubernetes_operator_for_machine_learning_pipelines_tpu
REGISTRY ?= tpumlops
TAG      ?= latest
DOCKER   ?= docker

.PHONY: images operator-image server-image router-image router-bin \
        install uninstall test test-fast test-e2e test-all lint \
        metrics-contract compile-budget plan-contract metrics-catalog verify

images: operator-image server-image router-image

operator-image:
	$(DOCKER) build -f $(PKG)/deploy/docker/Dockerfile.operator \
	  -t $(REGISTRY)/operator:$(TAG) .

server-image:
	$(DOCKER) build -f $(PKG)/deploy/docker/Dockerfile.server \
	  -t $(REGISTRY)/jax-server:$(TAG) .

router-image:
	$(DOCKER) build -f $(PKG)/deploy/docker/Dockerfile.router \
	  -t $(REGISTRY)/router:$(TAG) .

# Local (no docker): compile the native router with the system toolchain.
router-bin:
	mkdir -p build
	g++ -O2 -std=c++17 -Wall -o build/router $(PKG)/native/router.cc

# Cluster install, mirroring the reference's README steps (:25-64):
# CRD -> RBAC -> operator Deployment.  Assumes the mlflow-creds secret
# exists in tpumlops-system (MLFLOW_TRACKING_URI + credentials).
install:
	kubectl apply -f $(PKG)/deploy/crd.yaml
	kubectl apply -f $(PKG)/deploy/rbac.yaml
	kubectl apply -f $(PKG)/deploy/operator-deployment.yaml

uninstall:
	kubectl delete -f $(PKG)/deploy/operator-deployment.yaml --ignore-not-found
	kubectl delete -f $(PKG)/deploy/rbac.yaml --ignore-not-found
	kubectl delete -f $(PKG)/deploy/crd.yaml --ignore-not-found

# Cost tranches (VERDICT r3 #10): `test-fast` is the unit core (~3 min);
# `test-all` adds the e2e (live servers / envtest apiserver) and slow
# (compile- and subprocess-heavy) tranches.
test: test-fast

test-fast:
	python -m pytest tests/ -x -q -m "not e2e and not slow"

test-e2e:
	python -m pytest tests/ -x -q -m "e2e or slow"

test-all:
	python -m pytest tests/ -x -q

# Ruff (config in pyproject.toml [tool.ruff]): pyflakes/pycodestyle
# error classes over the first-party tree.  Soft dependency — the
# serving image does not bake a linter, so environments without ruff
# skip with a notice instead of failing verify (CI images install it:
# `pip install ruff`).
lint:
	@if command -v ruff >/dev/null 2>&1; then \
	  ruff check .; \
	else \
	  echo "lint: ruff not installed; skipping (pip install ruff)"; \
	fi

# Metric-identity contract (SURVEY §7 hard part 4): the promotion
# gate's PromQL — and every dashboard/alert — reads these exact family
# names and label sets.  A tier-1 test file; this is its own command.
metrics-contract:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_metrics_contract.py -q

# Compile-budget regression gate (ISSUE 16): the unified super-step
# engine's whole point is a small program space.  Runs both warmup
# sweeps on the tiny model with the compile observatory attached and
# fails if the unified jit-variant count, the legacy/unified collapse
# ratio, or the compile-seconds total regresses past the committed
# budget in COMPILE_BUDGET.json.
compile-budget:
	env JAX_PLATFORMS=cpu python scripts/check_compile_budget.py

# Plan contract (ISSUE 18): the offline SLO planner's output is a pure
# function of (trace, objective, cost model, grid) — re-planning the
# committed fixture trace must reproduce the committed plan JSON
# byte-for-byte.  tests/test_planner.py holds the same in tier-1; this
# is its command-line form.
plan-contract:
	env JAX_PLATFORMS=cpu python scripts/plan.py --dry-run \
	  --expect tests/fixtures/journey_plan.json > /dev/null

# Metrics-catalog lint (ISSUE 20): the three OBSERVABILITY.md series
# tables must enumerate EXACTLY the families the server / operator /
# router planes export — both directions.  Tier-1 runs the same check
# (tests/test_metrics_contract.py); this is its command-line form.
metrics-catalog:
	env JAX_PLATFORMS=cpu python scripts/check_metrics_catalog.py

# What stands outside tier-1 (lint, and the compile-budget sweep: ROADMAP
# D12), the catalog's command-line form, then the tier-1 command as the
# driver runs it: not-slow tranche, six xdist workers a file at a time,
# collection errors tolerated, 1470 s wall cap, the pass count from the
# junit file.  (The driver also sets ALLOW_MULTIPLE_LIBTPU_LOAD=1; the
# suite does not need it: one file describes the TPU, in a fixture.)
verify: lint compile-budget metrics-catalog
	set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; \
	timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml \
	  -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$${PIPESTATUS[0]}; \
	said=$$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null \
	  | head -n 1 | awk '{n=$$1-$$2-$$3-$$4; print (n<0 ? 0 : n)}'); \
	echo DOTS_PASSED=$${said:-$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c)}; \
	exit $$rc
