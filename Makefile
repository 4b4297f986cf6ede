# One-word entry points for the verify / bench / lint loops.
#
#   make test        tier-1 suite (the invocation ROADMAP.md pins)
#   make test-mesh   multi-device suites under 4 forced host devices
#   make bench       out-of-core + mesh-farm + polish + CV-grid + disk-tier
#                    curves -> BENCH_streaming.json + BENCH_stage2_stream.json
#                    + BENCH_stage2_mesh.json + BENCH_polish.json +
#                    BENCH_cv_grid.json + BENCH_disk_stream.json
#   make bench-smoke same suites at smoke sizes (fast CI loop) + the
#                    observability smoke (trace coverage / no-op / overhead)
#   make trace-smoke just the observability smoke -> /tmp/trace_smoke.json
#   make bench-all   every benchmark suite (paper tables + streaming)
#   make lint        byte-compile + import smoke over all python trees
#
# Tests run on the CPU (JAX_PLATFORMS=cpu; Pallas kernels in interpret mode).
# The chip path is `python chip_smoke.py` (`--chips 4` for the stage-2 farm).

PY       ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-mesh bench bench-smoke bench-all trace-smoke lint

test:
	$(PY) -m pytest -x -q

# The subprocess helpers inside these files force their own child device
# counts; the env var here additionally multi-devices the in-process parts.
# test_shards.py rides along: the shard chaos suite (torn writes, bit-flips,
# IO faults) includes 2-device farm parity from a shard-backed G.
test-mesh:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	$(PY) -m pytest -x -q tests/test_stage2_mesh.py tests/test_block_cache.py \
	tests/test_resilience.py tests/test_shards.py

# The stage-2 farm suite runs each device count in one process over
# jax.local_devices()[:D]; on the CPU those devices are virtual and must be
# in XLA_FLAGS before jax starts, hence its own process.  Single-threaded
# eigen pins one compute thread per virtual device, so device parallelism
# comes only from the farm itself.
MESH_XLA_FLAGS := --xla_cpu_multi_thread_eigen=false \
	--xla_force_host_platform_device_count=4

bench:
	$(PY) -m benchmarks.run streaming stage2 polish table3 disk
	XLA_FLAGS="$(MESH_XLA_FLAGS)" $(PY) -m benchmarks.run stage2_mesh

# smoke-sized records must not clobber the committed BENCH_*.json trajectory
bench-smoke:
	BENCH_SMOKE=1 \
	BENCH_STREAMING_JSON=/tmp/BENCH_streaming.smoke.json \
	BENCH_STAGE2_STREAM_JSON=/tmp/BENCH_stage2_stream.smoke.json \
	BENCH_POLISH_JSON=/tmp/BENCH_polish.smoke.json \
	BENCH_CV_GRID_JSON=/tmp/BENCH_cv_grid.smoke.json \
	BENCH_DISK_STREAM_JSON=/tmp/BENCH_disk_stream.smoke.json \
	$(PY) -m benchmarks.run streaming stage2 polish table3 disk trace_smoke
	BENCH_SMOKE=1 BENCH_STAGE2_MESH_JSON=/tmp/BENCH_stage2_mesh.smoke.json \
	XLA_FLAGS="$(MESH_XLA_FLAGS)" $(PY) -m benchmarks.run stage2_mesh

# streamed fit under a Tracer: asserts >=1 span per core pipeline category
# in the exported Chrome-trace JSON, zero events on the disabled path, and
# bounded NULL-tracer overhead
trace-smoke:
	$(PY) -m benchmarks.run trace_smoke

bench-all:
	$(PY) -m benchmarks.run

lint:
	$(PY) -m compileall -q src tests benchmarks examples
	$(PY) -c "import repro, repro.core, repro.kernels, repro.launch, \
	repro.models, repro.baselines, repro.data, repro.analysis"
