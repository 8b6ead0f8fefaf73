# Build/test/generate entrypoints (role of the reference's Makefile:108-209).

PYTHON ?= python
OUTPUT_DIR ?= ../consensus-spec-tests
GENERATORS = operations sanity finality rewards random forks epoch_processing \
             genesis ssz_static bls shuffling light_client kzg_4844 \
             kzg_7594 fork_choice merkle_proof ssz_generic sync transition

.PHONY: test citest test-crypto bench bench-all bench-merkle-smoke \
        bench-forkchoice-smoke bench-obs-smoke bench-block-smoke \
        bench-state-smoke bench-supervisor-smoke bench-das-smoke \
        bench-mesh-smoke bench-recovery-smoke bench-sanitizer-smoke \
        bench-serving-smoke bench-corpus-smoke bench-telemetry-smoke \
        sim-smoke sim-heavy \
        obs-report dryrun warm native lint lint-changed lint-verdicts \
        speclint-baseline \
        generate_tests $(addprefix gen_,$(GENERATORS)) clean-vectors pyspec \
        corpus corpus-check

# fast local suite: signature checks off except @always_bls
# (reference `make test`, Makefile:118-120)
test:
	$(PYTHON) -m pytest tests/ -q

# CI tier: every signature verified through the fastest available
# backend — native C when gcc can build it, else jax, else the py
# oracle.  Hosts without gcc degrade (loudly) to a slower backend
# instead of erroring out of the whole tier; when gcc IS present a
# broken native build fails the tier rather than silently falling back
# (a stale .so from an earlier build would otherwise mask the breakage)
# (reference `make citest` with --bls-type=fastest, Makefile:129-137).
# A host-CPU tier: JAX_PLATFORMS=cpu unless the caller chose otherwise.
citest: export JAX_PLATFORMS ?= cpu
citest:
	@if command -v gcc >/dev/null 2>&1; then $(MAKE) native; \
	else echo "citest: gcc not found — skipping native build," \
	          "degrading to the jax/python backends" >&2; fi
	$(PYTHON) benchmarks/bench_merkle_smoke.py
	$(PYTHON) benchmarks/bench_fork_choice.py --smoke
	$(PYTHON) benchmarks/bench_block_verify.py --smoke
	$(PYTHON) benchmarks/bench_state_arrays.py --smoke
	$(PYTHON) benchmarks/bench_supervisor.py
	$(PYTHON) benchmarks/bench_das.py
	$(PYTHON) benchmarks/bench_mesh.py
	$(PYTHON) benchmarks/bench_recovery.py
	$(PYTHON) benchmarks/bench_sanitizer.py
	$(PYTHON) benchmarks/bench_serving.py --smoke
	$(PYTHON) benchmarks/bench_corpus.py --smoke
	$(MAKE) sim-smoke
	$(PYTHON) -m pytest tests/ -q --enable-bls --bls-type fastest

# static checks: syntax gate + the speclint whole-program analyzer
# (style, uint64-hazard + U9xx range proving, jax-tracing,
# ladder-drift, spec-markdown, observability, state-layer,
# counted-fallback, supervision, determinism, engine-coverage) in one
# process — role of the reference `make lint` (Makefile:153-158,
# flake8+mypy; neither ships in this image).  Exits 0 modulo the
# checked-in ratchet file speclint_baseline.json.  Warm reruns serve
# findings from the content-hash incremental store
# (.speclint_cache.json, gitignored).  The compiled ladder is generated (gitignored):
# build it if absent so fresh clones lint out of the box, but never
# overwrite an existing tree (a drifted or hand-edited one must stay
# visible to the L3xx pass).
lint:
	$(PYTHON) -m compileall -q consensus_specs_tpu tests generators benchmarks
	@test -d consensus_specs_tpu/forks/compiled || $(MAKE) pyspec
	$(PYTHON) -m consensus_specs_tpu.tools.speclint .

# the pre-commit developer loop (docs/static-analysis.md): lint only
# the files dirty vs the git index; the tree passes (ladder,
# determinism, coverage, effects) stay warm through the dependency-
# granular cache unless a file they actually read changed
lint-changed:
	$(PYTHON) -m consensus_specs_tpu.tools.speclint . --changed

# the two CI proof gates on their own (both baseline-zero): the E12xx
# commit-scope/psum/write-ordering verdicts and the N13xx per-dispatch-
# path host-work budget (every mesh path proven O(S) host work —
# docs/static-analysis.md, docs/sharding.md)
lint-verdicts:
	$(PYTHON) -m consensus_specs_tpu.tools.speclint . --effect-verdicts
	$(PYTHON) -m consensus_specs_tpu.tools.speclint . --cost-verdicts

# intentionally re-record the speclint debt (after paying some down, or
# with a written justification for new findings in the PR).
# `make speclint-baseline PASSES=uint64,ranges` re-ratchets only the
# named passes: every other pass's recorded debt is carried over
# untouched (the driver keeps their baseline keys).
speclint-baseline:
	$(PYTHON) -m consensus_specs_tpu.tools.speclint . --write-baseline \
		$(if $(PASSES),--passes $(PASSES))

# crypto kernels incl. the heavy differential tier — one pytest
# process per file: the big XLA programs (pairing, sharded verify,
# batched SHA) each claim gigabytes during compile, and accumulating
# them in one interpreter can exhaust the 1-core host mid-run
CRYPTO_SUITES = tests/test_bls.py tests/test_bls_rlc.py \
	tests/test_native_bls.py \
	tests/test_numpy_kernels.py tests/test_hash_to_curve.py \
	tests/test_sha256_kernel.py tests/test_curdleproofs.py \
	tests/test_jax_bls.py tests/test_multichip.py tests/deneb/kzg

test-crypto:
	@set -e; for s in $(CRYPTO_SUITES); do \
		echo "=== $$s"; CS_TPU_HEAVY=1 $(PYTHON) -m pytest $$s -q; \
	done

bench:
	$(PYTHON) bench.py

bench-all:
	$(PYTHON) benchmarks/bench_all.py

# epoch-engine smoke: loop-vs-vectorized rewards at the small registry
# shape (full matrix: --epoch-shapes 16384,262144,1048576)
bench-epoch:
	$(PYTHON) benchmarks/bench_all.py --configs 5 --epoch-shapes 16384

# merkle-engine dispatch smoke: registry-wide commits must re-hash
# through the batched paths (asserted via the utils/ssz/merkle counters;
# nonzero exit on a per-pair hashlib regression).  Native build is
# best-effort: without it the smoke installs the JAX batched hasher.
bench-merkle-smoke:
	-$(MAKE) native
	$(PYTHON) benchmarks/bench_merkle_smoke.py

# fork-choice dispatch smoke: head recomputes must run through the
# proto-array engine (ZERO spec-loop fallbacks) and match the spec loop
# byte-for-byte on every churn round (asserted via the
# forkchoice/proto_array counters; nonzero exit on regression)
bench-forkchoice-smoke:
	$(PYTHON) benchmarks/bench_fork_choice.py --smoke

# whole-block signature-verification smoke: the deferred flush must
# take the RLC path with EXACTLY one pairing for the block (asserted
# via the bls.flush/bls.pairings counters; nonzero exit on regression),
# agree with the lane path + python oracle on a tampered-item matrix,
# and report lane-vs-RLC and oracle-vs-RLC ratios
bench-block-smoke:
	-$(MAKE) native
	$(PYTHON) benchmarks/bench_block_verify.py --smoke

# state-arrays store smoke: the copy-on-write column store must show
# at most one registry extraction per epoch transition, exactly one
# balance-family commit per transition, and N forked replays sharing
# one base snapshot with byte-identical roots (counter-asserted via the
# state_arrays.* metrics; nonzero exit on regression)
bench-state-smoke:
	$(PYTHON) benchmarks/bench_state_arrays.py --smoke

# adversarial sweep acceptance (docs/simulator.md): >= 200 seeded
# hostile scenarios complete engines-on; every injected fault counted
# on its reason=injected series (zero silent fallbacks), every
# injected/storm/spec-differential leg byte-identical to its
# uninjected replay; nonzero exit + minimized repro artifacts under
# sim_artifacts/ on any violation.  The time budget converts a
# pathological host into a controlled failure instead of a CI hang.
sim-smoke:
	$(PYTHON) -m consensus_specs_tpu.sim.sweep --seeds 200 \
		--min-scenarios 200 --time-budget 2400
	CS_TPU_SANITIZER=1 $(PYTHON) -m consensus_specs_tpu.sim.sweep \
		--seeds 24 --min-scenarios 24 --start 9000 \
		--recovery-seeds 1 --time-budget 600

# the CS_TPU_HEAVY nightly shape: a thousand seeds on a denser
# injection cadence with more real-signature seeds, then the cross-leg
# with proto-array AND the state-arrays store off (spec-loop fork
# choice + detached columns) so the remaining engines are swept against
# the pure-spec composition too
sim-heavy:
	$(PYTHON) -m consensus_specs_tpu.sim.sweep --seeds 1000 \
		--inject-every 4 --max-sites 6 --diff-every 8 --bls-seeds 4
	CS_TPU_PROTO_ARRAY=0 CS_TPU_STATE_ARRAYS=0 \
		$(PYTHON) -m consensus_specs_tpu.sim.sweep --seeds 250 \
		--start 5000 --inject-every 8 --diff-every 10 --bls-seeds 2

# telemetry disabled-path overhead: with CS_TPU_PROFILE/CS_TPU_TRACE
# unset, the span + counter instrumentation across the engine stack
# must cost <2% of the 32-slot replay (exact op census x measured
# per-op cost; nonzero exit above the bound).  Also bounds the flight
# recorder (disarmed record cost x armed-replay census <2%) and
# asserts a flight+trace-armed serving replay byte-identical to the
# synchronous oracle.
bench-obs-smoke:
	$(PYTHON) benchmarks/bench_obs_overhead.py

# live telemetry plane smoke (docs/observability.md): obs.serve must
# answer /metrics, /healthz and /snapshot (schema-checked) WHILE a
# pipelined serving replay runs, without moving a byte of consensus
# state; a forced quarantine must flip /healthz to 503 and a
# supervisor reset must restore it
bench-telemetry-smoke:
	$(PYTHON) benchmarks/bench_telemetry.py

# DAS engine smoke (docs/das.md): a multi-blob cell-proof batch must
# verify in exactly ONE pairing check (ZERO of its own inside an RLC
# scope — the block's single flush pairing carries it), batched
# multi-blob erasure recovery must beat the per-blob spec-markdown
# loop byte-identically, and the CS_TPU_DAS=0 wrapper overhead must
# stay under the 2% bound (counter-asserted; nonzero exit on any
# regression).  Native build is best-effort — the engine folds and the
# spec loop both degrade to the python pairing oracle without it.
bench-das-smoke:
	-$(MAKE) native
	$(PYTHON) benchmarks/bench_das.py

# mesh-engine smoke (docs/sharding.md): on the 8-way host-device mesh
# (XLA_FLAGS below), a full epoch transition must run every
# sub-transition through the SPMD programs with EXACTLY the budgeted
# psum count per sub-transition (mesh.psums counter-asserted against
# mesh_epoch.PSUM_BUDGET, psum census proven structurally on the
# jaxprs), commit byte-identical state roots mesh-on vs mesh-off vs
# spec loop, and show near-linear (>= 6x at 8 shards) per-shard kernel
# scaling on 1M-validator columns; nonzero exit on any regression
bench-mesh-smoke:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PYTHON) benchmarks/bench_mesh.py

# block-serving pipeline smoke (docs/serving.md): the pipelined lane
# (window batching + overlapped RLC flush + chunk-level clones) must
# replay the captured adversarial load streams byte-identical to the
# synchronous per-block lane (deep store digests + per-block verdicts),
# fold to EXACTLY one pairing per window (bls.pairings ==
# serving.windows, strictly below the sync lane's per-block count),
# keep the one-commit-per-epoch census lane-identical under overlap,
# and sustain strictly more slots/sec; chunk-level clone_state must
# beat state.copy() root-identically.  Native build is best-effort —
# the lanes degrade together to a slower signature backend without it.
bench-serving-smoke:
	-$(MAKE) native
	$(PYTHON) benchmarks/bench_serving.py --smoke

# durable-replay smoke (docs/recovery.md): checkpoint save/restore +
# journal tail replay round-trip byte-identical (counter-asserted:
# restore really served from a checkpoint generation), restore +
# tail-replay cost measured and reported, and the checkpoint-DISABLED
# wrapper overhead bound: with CS_TPU_CHECKPOINT=0 the durable step
# driver must cost <2% over the plain replay (the obs/supervisor
# discipline; nonzero exit above the bound)
bench-recovery-smoke:
	$(PYTHON) benchmarks/bench_recovery.py

# runtime effect-sanitizer smoke (docs/static-analysis.md): the
# disabled hooks must cost <2% of the 32-slot replay (census x per-op
# cost), an ARMED replay must be byte-identical to the disarmed one
# with zero violations, and the armed leg must book nonzero
# sanitizer.checks (non-vacuous); nonzero exit on any violated bound
bench-sanitizer-smoke:
	$(PYTHON) benchmarks/bench_sanitizer.py

# engine-supervisor smoke (docs/robustness.md): counter-asserted
# breaker lifecycle on a real dispatch site (threshold trips ->
# open -> skip -> half-open probe -> closed; corrupt-mode result +
# rate-1 sentinel audit -> quarantine + artifact), then the
# enabled-path overhead bound: supervisor ON must cost <2% of the
# 32-slot replay (exact call census x measured per-op cost, the
# bench_obs_overhead discipline; nonzero exit above the bound)
bench-supervisor-smoke:
	$(PYTHON) benchmarks/bench_supervisor.py

# human telemetry view: 32-slot replay with full tracing, span tree +
# metric catalog (see docs/observability.md; --format json|prom for the
# machine exporters)
obs-report:
	$(PYTHON) -m consensus_specs_tpu.tools.obs_report

# the sharded verification step on an 8-device virtual host-CPU mesh
dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PYTHON) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# prewarm the persistent XLA compile cache (.jax_cache/, or
# $JAX_COMPILATION_CACHE_DIR) with the programs bench.py, the RLC flush
# and the graft entry dispatch, on whatever platform JAX reports
warm:
	$(PYTHON) -m consensus_specs_tpu.tools.warm

# compile the markdown specs into importable modules (reference `make pyspec`)
pyspec:
	$(PYTHON) -m consensus_specs_tpu.compiler

# vector generation (reference `make generate_tests` / `make gen_<name>`)
generate_tests: $(addprefix gen_,$(GENERATORS))

$(addprefix gen_,$(GENERATORS)): gen_%:
	$(PYTHON) generators/$*/main.py -o $(OUTPUT_DIR)

# corpus factory (docs/corpus.md): every generator through ONE shared
# fork-start pool — pre-warmed parent image (spec ladders, genesis
# states, pubkeys inherited copy-on-write), cost-aware longest-first
# schedule from the persisted per-case timing profile, per-case RLC
# signature folds with synchronous replay on any failed fold.
# Byte-identical to `make generate_tests` (bench_corpus asserts the
# tree digests); resume semantics unchanged (INCOMPLETE cases redone,
# complete cases skipped)
corpus:
	$(PYTHON) -m consensus_specs_tpu.gen.corpus -o $(OUTPUT_DIR)

# corpus fidelity replay (docs/corpus.md): re-execute the emitted
# operations/epoch_processing/sanity/finality vectors through the spec
# twice — engines on, then every CS_TPU_* switch forced off — proving
# no engine leaked an optimistic result into a vector; nonzero exit on
# any mismatch in either leg
corpus-check:
	$(PYTHON) -m consensus_specs_tpu.gen.replay -o $(OUTPUT_DIR)
	CS_TPU_VECTORIZED_EPOCH=0 CS_TPU_PROTO_ARRAY=0 \
	CS_TPU_STATE_ARRAYS=0 CS_TPU_BLS_RLC=0 CS_TPU_HASH_FOREST=0 \
	CS_TPU_SUPERVISOR=0 CS_TPU_DAS=0 CS_TPU_MESH=0 \
	CS_TPU_CHECKPOINT=0 CS_TPU_SERVING=0 \
		$(PYTHON) -m consensus_specs_tpu.gen.replay -o $(OUTPUT_DIR)

# corpus factory smoke (docs/corpus.md): bounded subset generated both
# ways — serial per-generator processes vs the one-pool factory — with
# tree digests compared byte-for-byte, plus the counter-asserted
# censuses: sign memo engages (gen.sign_memo hits > 0), folded cases
# collapse to at most one RLC pairing each (bls.flush{path=rlc} <=
# gen.case_batches{path=folded}, total pairings strictly below the
# unfolded run), and expected-invalid cases fall back through
# gen.case_replays; nonzero exit on any violation
bench-corpus-smoke:
	$(PYTHON) benchmarks/bench_corpus.py --smoke

# native C components (raw-snappy codec for vector IO, SHA-256 merkle
# layer hasher for host-side merkleization, BLS12-381 signature backend
# — the reference's milagro/arkworks role; constants generated from the
# python oracle by csrc/gen_bls_consts.py)
native:
	gcc -O2 -shared -fPIC -o csrc/libcsnappy.so csrc/snappy.c
	gcc -O3 -shared -fPIC -o csrc/libcsha256.so csrc/sha256_merkle.c
	gcc -O2 -shared -fPIC -o csrc/libcbls12381.so csrc/bls12_381.c

bls-consts:
	$(PYTHON) csrc/gen_bls_consts.py > csrc/bls12_381_consts.h

clean-vectors:
	rm -rf $(OUTPUT_DIR)/tests
