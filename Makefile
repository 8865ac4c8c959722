# Build/CI entry points — reference makefile:24-25 (`make test`) plus
# the bench and demo-testnet drivers, and `make dist` as the
# counterpart of the reference's release build (scripts/dist.sh).
PY ?= python

.PHONY: test test-fast test-crash bench demo conf run bombard watch stop dist

dist:
	$(PY) -m build

test:
	$(PY) -m pytest tests/ -q

test-fast:
	$(PY) -m pytest tests/ -q -m "not slow"

test-crash:
	$(PY) -m pytest tests/test_crash.py tests/test_durability.py -q

bench:
	python3 benchmark/run.py --workload v1024-replay

demo:
	demo/scripts/demo.sh

conf:
	demo/scripts/conf.sh

run:
	demo/scripts/run-testnet.sh

bombard:
	demo/scripts/bombard.sh

watch:
	demo/scripts/watch.sh

stop:
	demo/scripts/stop.sh
