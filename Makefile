.PHONY: test test-fast bench-fig8 example-serve

# Tier-1 verify: full suite on the CPU (kernels interpret; the TPU compile
# tests compile for a described v5e chip — see tests/README.md)
test:
	PYTHONPATH=src python -m pytest -q

# quick signal: skip the slowest end-to-end modules
test-fast:
	PYTHONPATH=src python -m pytest -q --ignore=tests/test_system.py \
		--ignore=tests/test_dryrun.py

bench-fig8:
	PYTHONPATH=src:. python benchmarks/fig8_throughput.py

example-serve:
	PYTHONPATH=src python examples/serve_pipedec.py
