# Budgeted test lanes (reference: Makefile:26-58). Lane membership lives in
# tests/lanes.py — the single source of truth, guarded by tests/test_lanes.py.
#
#   make test-fast          unit core             (~5 min on a 1-core box)
#   make test-models        model zoo + HF parity (~12 min)
#   make test-subproc       CLI + example scripts (~12 min)
#   make test-multiprocess  real jax.distributed  (~8 min)
#   make test-all           default suite, no -x (one flake can't hide the rest)
#   make test-nightly       + exhaustive nightly variants (-m "")
#   make chaos              self-healing drill: supervisor + chaos tests, slow incl.
#
# Dev loop: run test-fast after every change; the others before a commit
# that touches their area; test-all before shipping. Exhaustive
# parametrizations are @pytest.mark.nightly (excluded by pyproject addopts).

PYTHON ?= python

.PHONY: test-fast test-models test-subproc test-multiprocess test-all test-nightly chaos quality serve-demo loadtest chip-smoke

test-fast:
	$(PYTHON) -m pytest -q $$($(PYTHON) tests/lanes.py fast)

test-models:
	$(PYTHON) -m pytest -q $$($(PYTHON) tests/lanes.py models)

test-subproc:
	$(PYTHON) -m pytest -q $$($(PYTHON) tests/lanes.py subproc)

test-multiprocess:
	$(PYTHON) -m pytest -q $$($(PYTHON) tests/lanes.py multiprocess)

test-all:
	$(PYTHON) -m pytest -q tests/

test-nightly:
	$(PYTHON) -m pytest -q -m "" tests/

# The full chaos drill: supervisor watchdog/restart/breaker units plus the
# slow self-healing scenarios (hang fence, mid-prefill kill, soak).
chaos:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -q -m "" tests/test_serving_supervisor.py

quality:
	$(PYTHON) -m compileall -q accelerate_tpu bench.py chip_smoke.py __graft_entry__.py

# The quickest proof that the trainer and the server start on the chip
# (one TPU v5e; exits non-zero anywhere else). See README "Running on the chip".
chip-smoke:
	$(PYTHON) chip_smoke.py

# Open-loop SSE load against a self-hosted tiny fleet (asyncio front
# end): heavy-tailed arrivals, goodput/TTFT/conformance JSON report,
# non-zero exit on any overload-conformance violation.
loadtest:
	JAX_PLATFORMS=cpu $(PYTHON) -m accelerate_tpu.commands.accelerate_cli loadtest \
		--n-streams 500 --rps 200 --out-tokens 8 --out-max 24 --prompt-len 8 \
		--prompt-max 32 --wall-deadline 120 --check

# HTTP gateway demo on a tiny random model (CPU): 2 replicas on :8000.
# Try: curl -s localhost:8000/readyz; curl -s -XPOST localhost:8000/v1/completions \
#        -d '{"prompt": [1,2,3,4], "max_new_tokens": 8, "seed": 0}'
serve-demo:
	JAX_PLATFORMS=cpu $(PYTHON) -m accelerate_tpu.commands.accelerate_cli serve \
		--model tiny --replicas 2 --port 8000 --max-len 128 --prefill-chunk 32 \
		--eos-token-id 7
