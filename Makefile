# Developer entry points (the reference drives its dev environment from a
# Makefile too: reth devnet + redis + tmux service panes; here the whole
# cluster is one process).

PY ?= python

# Native engine codegen flags. -march=x86-64-v2 (not -march=native): the
# .so must load on any CI/prod host, and sanitizer stacks want a stable
# ISA — the AVX2/AVX-512 kernels are compiled in via per-function target
# attributes and selected at RUNTIME, so one baseline .so carries every
# ISA. -ffp-contract=off: no silent a*b+c fusion — every fma is explicit,
# one float pipeline per ISA (the determinism contract). Override for
# tuned local builds: make native NATIVE_CFLAGS="-O3 -march=native -ffp-contract=off"
# (protocol_tpu/native/__init__.py honors the same env var).
NATIVE_CFLAGS ?= -O3 -march=x86-64-v2 -ffp-contract=off
NATIVE_BASE = -std=gnu++17 -pthread -shared -fPIC
# sanitizer builds: -O1 -g keeps symbols/line numbers in reports and the
# slowdown usable; separate .so names so they never clobber the prod build
NATIVE_SAN_CFLAGS ?= -O1 -g -march=x86-64-v2 -ffp-contract=off

.PHONY: test test-fast native native-tsan native-asan native-avx2 native-avx512 sanitize devnet devnet-persistent bench bench-scaling chip-smoke clean lint

test:
	$(PY) -m pytest tests/ -q

test-fast:
	$(PY) -m pytest tests/ -q -x -m "not slow"

# native CPU assignment engine (ctypes-loaded shared library; -pthread
# for the multi-threaded engine=native-mt variants)
native:
	g++ $(NATIVE_CFLAGS) $(NATIVE_BASE) -o native/libassign_engine.so native/assign_engine.cpp

# sanitizer-instrumented variants (selected at runtime via
# PROTOCOL_TPU_NATIVE_SANITIZE=tsan|asan; driven end-to-end by
# scripts/sanitize_native.py, which LD_PRELOADs the matching runtime)
native-tsan:
	g++ $(NATIVE_SAN_CFLAGS) -fsanitize=thread $(NATIVE_BASE) -o native/libassign_engine.tsan.so native/assign_engine.cpp

native-asan:
	g++ $(NATIVE_SAN_CFLAGS) -fsanitize=address,undefined -fno-sanitize-recover=all $(NATIVE_BASE) -o native/libassign_engine.asan.so native/assign_engine.cpp

# ISA-default variants (selected at runtime via
# PROTOCOL_TPU_NATIVE_ISA_VARIANT=avx2|avx512): identical codegen — every
# .so carries all per-ISA kernels — but the baked DEFAULT dispatch differs,
# for hosts where no env plumbing reaches the process. The runtime clamp
# still falls back to what the CPU supports. PROTOCOL_TPU_NATIVE_ISA
# overrides the baked default in any variant.
native-avx2:
	g++ $(NATIVE_CFLAGS) -DENGINE_DEFAULT_ISA=1 $(NATIVE_BASE) -o native/libassign_engine.avx2.so native/assign_engine.cpp

native-avx512:
	g++ $(NATIVE_CFLAGS) -DENGINE_DEFAULT_ISA=2 $(NATIVE_BASE) -o native/libassign_engine.avx512.so native/assign_engine.cpp

# TSan stress gate over all three -mt kernels (threads 1/2/4/8, churned
# warm-arena re-solves); add --sanitizer asan for the memory/UB pass
sanitize:
	$(PY) scripts/sanitize_native.py --sanitizer tsan

# one-command local cluster: ledger API + discovery + orchestrator +
# validator + workers. See python -m protocol_tpu.devnet --help.
devnet:
	$(PY) -m protocol_tpu.devnet --workers 2 --cpu

# persistent devnet: docker runtime + remote scheduler seam + AOF/ledger
# state surviving restarts
devnet-persistent:
	$(PY) -m protocol_tpu.devnet --workers 2 --cpu --runtime docker \
	  --scheduler-backend remote --state-dir /var/tmp/protocol_tpu_devnet

# the scheduler-kernel benchmark: measures the TPU and FAILS without one
# (a CPU measurement is asked for by name: bench.py engine=native-mt)
bench:
	$(PY) bench.py

# ladder-#4 scaling measurement (per-shard rates + HBM envelopes; see
# SCALING.md). Fails without a TPU; add --cpu for the virtual CPU mesh.
bench-scaling:
	$(PY) bench_scaling.py --full

# the quickest proof the served path starts on the chip (one process,
# one session end to end at 32k x 32k; fails without a TPU)
chip-smoke:
	$(PY) chip_smoke.py

# full-scale matcher tests (100k nodes x 10k slots; ~4 min on CPU)
scale-tests:
	PROTOCOL_TPU_SCALE_TESTS=1 $(PY) -m pytest tests/test_scale_matcher.py -v

# fail-the-build lint discipline: the hermetic unused-import gate, the
# project rule engine (determinism / lock / dtype / dense-alloc
# contracts — scripts/lints/), and the whole-program analyzer
# (lock-order / protocol-sm / jax-purity / jax-retrace / spmd-contract
# — scripts/analysis/)
lint:
	$(PY) scripts/lint.py
	$(PY) -m scripts.lints
	$(PY) -m scripts.analysis

proto:
	protoc --python_out=. protocol_tpu/proto/scheduler.proto

clean:
	rm -rf native/libassign_engine.so native/libassign_engine.tsan.so \
	  native/libassign_engine.asan.so native/libassign_engine.avx2.so \
	  native/libassign_engine.avx512.so **/__pycache__ .pytest_cache
