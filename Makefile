PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: test smoke bench bench-paged bench-chunked bench-prefix \
	bench-decode bench-spec bench-goodput bench-chaos serve obs-smoke \
	chaos-smoke quickstart

test:                ## tier-1 suite
	python -m pytest -x -q

smoke:               ## tiny-config benchmark pass (continuous batching)
	python -m benchmarks.run --smoke

bench:               ## full benchmark suite (paper figures)
	python -m benchmarks.run

bench-paged:         ## paged KV arena vs dense merge vs sync data planes
	REPRO_BENCH_SMOKE=$${REPRO_BENCH_SMOKE:-0} \
	REPRO_BENCH_SECTION=live,sim python -m benchmarks.continuous_batching

bench-chunked:       ## chunked vs unchunked prefill (head-of-line stall)
	REPRO_BENCH_SMOKE=$${REPRO_BENCH_SMOKE:-0} \
	REPRO_BENCH_SECTION=chunked python -m benchmarks.continuous_batching

bench-prefix:        ## radix prefix cache vs cold prefill (token reuse)
	REPRO_BENCH_SMOKE=$${REPRO_BENCH_SMOKE:-0} \
	REPRO_BENCH_SECTION=prefix python -m benchmarks.continuous_batching

bench-decode:        ## zero-gather paged decode vs dense-gather oracle
	REPRO_BENCH_SMOKE=$${REPRO_BENCH_SMOKE:-0} \
	REPRO_BENCH_SECTION=decode python -m benchmarks.continuous_batching

bench-spec:          ## speculative decode vs oracle (accepted/launch gate)
	REPRO_BENCH_SMOKE=$${REPRO_BENCH_SMOKE:-0} \
	REPRO_BENCH_SECTION=spec python -m benchmarks.continuous_batching

bench-goodput:       ## sdf admission + parking preemption vs fifo
	REPRO_BENCH_SMOKE=$${REPRO_BENCH_SMOKE:-0} \
	REPRO_BENCH_SECTION=goodput python -m benchmarks.continuous_batching

bench-chaos:         ## crash-mid-burst recovery vs failure-free oracle
	REPRO_BENCH_SMOKE=$${REPRO_BENCH_SMOKE:-0} \
	REPRO_BENCH_SECTION=chaos python -m benchmarks.continuous_batching

serve:               ## end-to-end serving driver
	python -m repro.launch.serve

chaos-smoke:         ## crash one server mid-burst; all rids must account
	python examples/serve_cluster.py --requests 9 --chaos

obs-smoke:           ## tiny traced+metered serve; validate the artifacts
	python -m repro.launch.serve --archs minicpm-2b --requests 6 \
		--max-new-tokens 4 --trace-out obs_trace.json \
		--metrics-out obs_metrics.prom \
		--calibrate-out obs_calibration.json
	python -c 'import json; from repro.obs import validate_chrome_trace, \
		parse_prometheus_text; \
		n = validate_chrome_trace(json.load(open("obs_trace.json"))); \
		m = parse_prometheus_text(open("obs_metrics.prom").read()); \
		c = json.load(open("obs_calibration.json")); \
		print("obs-smoke ok:", n, "trace events,", len(m), \
		      "series, overrides:", c["sim_config_overrides"])'

quickstart:
	python examples/quickstart.py
