# Native io library + sanitizer/test targets.
# The Python side builds build/libgoleftio.so lazily; these targets are
# for CI-style hardening runs (SURVEY.md §5: host C++ under ASan/TSan).

CXX ?= g++
SRC = csrc/fastio.cpp

.PHONY: native asan tsan test test-native-asan test-native-tsan \
        serve-smoke obs-smoke chaos-smoke pairhmm-smoke fleet-smoke \
        fleet-obs-smoke federation-chaos profile-smoke memory-smoke \
        decode-smoke dataplane-smoke biobank-smoke mapper-smoke \
        lint lint-changed lint-ci plan-lint check clean

native: build/libgoleftio.so

# Fast BGZF inflate via libdeflate; on systems without it build with
#   make native DEFLATE_LIBS= EXTRA=-DNO_LIBDEFLATE
# (native.py's lazy build does the same two-attempt fallback itself)
DEFLATE_LIBS ?= -ldeflate

build/libgoleftio.so: $(SRC)
	mkdir -p build
	$(CXX) -O3 -march=native -shared -fPIC $(SRC) $(EXTRA) -lz $(DEFLATE_LIBS) -o $@

build/libgoleftio_asan.so: $(SRC)
	mkdir -p build
	$(CXX) -O1 -g -fsanitize=address -shared -fPIC $(SRC) $(EXTRA) -lz $(DEFLATE_LIBS) -o $@

asan: build/libgoleftio_asan.so

test:
	python -m pytest tests/ -q

# serve daemon end-to-end: start on an ephemeral port, one depth
# request through the client, validate the observability surface
# (/metrics SLO block + Prometheus encoding, /debug/flight span
# trees, a SIGUSR1 flight dump that parses), clean SIGTERM drain,
# exit 0. Pinned to the host platform inside (CI has no accelerator);
# whole run bounded by the smoke's own 120s deadline.
serve-smoke:
	python -m goleft_tpu.serve.smoke

# observability end-to-end: a real depth invocation with --trace-out +
# --metrics-out on a fabricated fixture, then schema-validate both
# artifacts (Chrome-trace-event shape Perfetto loads; run manifest
# with required provenance keys). Host-pinned like serve-smoke.
obs-smoke:
	python -m goleft_tpu.obs.smoke

# resilience end-to-end: a cohortdepth subprocess is SIGKILLed
# mid-flight by a deterministic injected fault, resumed via
# --checkpoint-dir/--resume to byte-identical output (journal replay
# proven through the run manifest's checkpoint counters), a
# permanently-corrupt sample is quarantined (exit 3, partial cohort
# byte-identical to a run without it) — then the serve
# legs against real daemons: poison isolation (one 400, seven
# byte-identical 200s), circuit-breaker trip/recover, watchdog
# re-queue of a hung pass, and a checkpoint:true request resuming
# byte-identically across a daemon SIGKILL+restart. Host-pinned like
# the other smokes.
chaos-smoke:
	python -m goleft_tpu.resilience.smoke

# the AST invariant analyzer over the whole package: determinism
# (sorted iteration where bytes/keys are produced), tracer hygiene in
# jitted code, lock discipline in the threaded modules (intra-class,
# cross-class foreign writes, package-wide lock-order cycles), thread
# and resource lifecycle, metrics-contract, exception classification,
# and the plan dispatch boundary. Fails on any non-baselined finding;
# `# gtlint: ok <rule-id> — reason` on a line is a reviewed waiver,
# .gtlint_baseline.json the grandfathered debt (docs/static-analysis.md).
# The wall-time budget is a pinned CI contract: rule growth that makes
# the gate crawl fails HERE, loudly, instead of silently taxing every
# `make check` (the parse pass parallelizes via --jobs; --stats prints
# the evidence).
LINT_BUDGET_S ?= 90
lint:
	python -m goleft_tpu lint --stats --max-seconds $(LINT_BUDGET_S)

# the fast pre-commit shape: lint only files changed vs git HEAD
lint-changed:
	python -m goleft_tpu lint --changed-only

# CI shape: same gate plus a SARIF 2.1.0 artifact (build/gtlint.sarif)
# for inline diff annotation
lint-ci:
	mkdir -p build
	python -m goleft_tpu lint --stats --max-seconds $(LINT_BUDGET_S) \
	    --sarif build/gtlint.sarif

# the dispatch-path-split regression gate: fails if any module outside
# goleft_tpu/plan/ calls execute_task or a raw RetryPolicy.call loop —
# the plan Executor is the ONE place retry/quarantine/checkpoint/
# faults/spans compose (docs/resilience.md). Now the AST-resolved
# plan-boundary rule (aliasing can't dodge it); `# plan-lint: ok` on a
# line is still the explicit reviewed waiver.
plan-lint:
	python -m goleft_tpu lint --only plan-boundary

# fleet end-to-end, all real subprocess daemons: (a) continuous
# batcher byte-identical to the window batcher and to the one-shot
# CLIs for depth/indexcov/cohortdepth/pairhmm; (b) two concurrent
# identical requests -> ONE device pass (cross-request step dedup,
# plan_steps_deduped_total) and two byte-identical 200s; (c) a worker
# SIGKILLed mid-flight -> router-level retry on the sibling ->
# byte-identical 200; (d) a tripped per-site breaker sheds only its
# own endpoint's traffic; (e) per-tenant quota exhaustion -> 429 with
# retry_after_s while other tenants are unaffected (and the
# retry-aware client honors the hint). Host-pinned like the others.
fleet-smoke:
	python -m goleft_tpu.fleet.smoke

# the supervisor chaos legs, all real subprocess daemons: a SIGKILL
# storm is healed to full capacity without operator action; a
# SIGSTOPped (hung) worker is detected by healthz timeout, SIGKILLed
# and recycled; a crash-looping slot is quarantined after K deaths
# (cohortdepth's manifest/exit-3 contract) while the remaining fleet
# serves byte-identical responses; a deterministic backlog scales the
# fleet up; a scale-down drain completes in-flight work
# byte-identically BEFORE the worker exits; and a --shared-cache
# request replayed after SIGKILL+restart hits the shared tier with
# zero device passes. Host-pinned like the other smokes.
fleet-chaos:
	python -m goleft_tpu.fleet.smoke --chaos

# device-resident entropy decode end-to-end: a CRAM cohort (two
# ORDER0 samples, one ORDER1 forcing the per-block host fallback)
# through real cohortdepth subprocesses — the --decode-device matrix
# is byte-identical to the default path, the run manifest carries the
# decode counters (device blocks, fallbacks, wire bytes compressed vs
# inflated), and an injected transient fault at the decode site is
# retried to identical bytes. Host-pinned like the other smokes.
decode-smoke:
	python -m goleft_tpu.ops.decode_smoke

# fleet observability plane end-to-end: a real subprocess router
# supervising two real serve workers (three OS processes). One depth
# request with a client-minted x-goleft-trace id yields ONE stitched
# trace from GET /fleet/trace/<id> — router forward span parenting the
# worker's request -> plan-step -> batch -> device-dispatch chain —
# with distinct Perfetto process tracks and the `goleft-tpu trace` CLI
# rendering it; /fleet/metrics counters equal the arithmetic sum of
# the live workers' counters in both encodings; and a SIGKILLed worker
# produces death/backoff/restart events replayable from the fsync'd
# events.jsonl (`goleft-tpu fleet events --json`, schema-stable) and
# visible in the router /metrics fleet.events block. Host-pinned like
# the other smokes.
fleet-obs-smoke:
	python -m goleft_tpu.obs.fleet_smoke

# the federation tier's contracts against real subprocess tiers (a
# federation router fronting two real fleets, each a supervised serve
# worker): a flooding tenant is shed at the federation front door
# (429 + honest retry_after_s, federation.tenant.burn_rate gauges in
# both /metrics encodings) while a quiet tenant's concurrent requests
# all land byte-identically; SIGKILL of one fleet's ROUTER mid-flight
# yields byte-identical 200s through the surviving fleet within the
# client's retry budget; and the healed fleet (router restarted in
# attach mode over its surviving worker) rejoins through a half-open
# probe and its affinity key routes home again. Host-pinned like the
# other smokes.
federation-chaos:
	python -m goleft_tpu.fleet.federation_smoke

# compile observatory + sampling profiler end-to-end: a real fleet
# (router + one supervised worker at --profile-hz 50) serves traced
# depth requests; /fleet/profile merges a non-empty window with
# goleft_tpu frames, /debug/compiles shows the cold depth dispatch as
# a ranked signature, `goleft-tpu warmup export` writes a validating
# manifest whose top signature is that hot bucket, and a SIGKILL-
# restarted worker's observatory proves the signature would cold-miss
# there — the exact miss a prewarmer consumes the manifest to
# prevent. Host-pinned like the other smokes.
profile-smoke:
	python -m goleft_tpu.obs.profile_smoke

# memory-plane leak sentinel: RSS bounded over >= 3 sampling windows
# while allocate/free rounds churn, a device family's live bytes
# return to baseline when its buffer dies, a deliberate hog trips the
# pressure band (real 503 + retry_after_s over HTTP) and recovers,
# and a fleet supervisor recycles a worker over --mem-recycle-mb with
# the memory_recycle event visible through the real events CLI.
# Host-pinned like the other smokes.
memory-smoke:
	python -m goleft_tpu.obs.memory_smoke

# object-store data plane end-to-end: the same CRAM/BAM cohorts staged
# in a loopback stub object store — cohortdepth/depth/indexcov CLIs
# byte-identical over https:// URLs vs local paths (--prefetch-depth
# and --decode-device composing), an injected transient fault at the
# fetch site retried to identical bytes, a 404'd object quarantining
# only its own sample (exit 3), mid-run ETag drift detected as
# stale-input (never silently mixed), a real serve worker
# byte-identical over URLs, and two real fleets with DISTINCT
# --shared-cache dirs behind a federation: cachesync replicates the
# warm entry, the home fleet is SIGKILLed, and the survivor answers
# byte-identically from the REPLICATED cache with zero device passes.
# Host-pinned like the other smokes.
dataplane-smoke:
	python -m goleft_tpu.io.dataplane_smoke

# biobank-scale cohort QC end-to-end: a 12-sample URL cohort over the
# stub object store scans byte-identical to local indexcov, appending
# 3 samples performs exactly 3×n_chroms QC computations (manifest-
# counter pinned), and a SIGKILL mid-scan resumes byte-identically
# from the checkpoint journal. Host-pinned like the other smokes.
biobank-smoke:
	python -m goleft_tpu.cohort.biobank_smoke

# the read mapper end-to-end: `goleft-tpu map --depth-out` maps
# >= 95% of 10k simulated 100-150bp reads to within +-5bp of their
# simulated origin; the fused depth bed is byte-identical to a
# --from-tuples re-derivation; a real serve daemon's /v1/map response
# carries the CLI's exact tuple/depth bytes; an injected transient
# fault at the map site retries to byte-identical tuples; and a FASTQ
# corrupted mid-stream maps everything before the bad record,
# quarantines the file and exits 3. Host-pinned like the other smokes.
mapper-smoke:
	python -m goleft_tpu.mapping.smoke

# the check-style aggregate: static gates first (cheap, loud), then
# the test suite, then the end-to-end proofs
check: lint plan-lint test decode-smoke dataplane-smoke \
       biobank-smoke fleet-smoke fleet-chaos fleet-obs-smoke \
       federation-chaos profile-smoke memory-smoke mapper-smoke

# pair-HMM stack end-to-end: emdepth exports CNV candidates
# (--candidates-out), the pairhmm CLI genotypes the planted het site
# over them, a real serve daemon's /v1/pairhmm response is
# byte-identical to the CLI, and an injected transient fault at the
# pairhmm dispatch site is retried to byte-identical output.
# Host-pinned like the other smokes.
pairhmm-smoke:
	python -m goleft_tpu.models.pairhmm_smoke

# run the io test files with the AddressSanitized library preloaded.
# Tests that execute XLA are excluded: ASan's allocator interposition is
# incompatible with the JAX runtime, so only the pure-io paths (which is
# all the C++ there is) run sanitized.
# only tests carrying the native_io marker run sanitized — the marker
# encodes the real invariant (no XLA execution under ASan; the allocator
# interposition crashes inside the JAX runtime)
test-native-asan: build/libgoleftio_asan.so
	GOLEFT_TPU_ASAN_LIB=$(CURDIR)/build/libgoleftio_asan.so \
	LD_PRELOAD=$(shell $(CXX) -print-file-name=libasan.so) \
	ASAN_OPTIONS=detect_leaks=0 \
	python -m pytest tests/ -q -m native_io

build/libgoleftio_tsan.so: $(SRC)
	mkdir -p build
	$(CXX) -O1 -g -fsanitize=thread -shared -fPIC $(SRC) $(EXTRA) -lz $(DEFLATE_LIBS) -o $@

tsan: build/libgoleftio_tsan.so

# ThreadSanitizer run over the same native_io suite — the decode
# threads share the lib's thread_local pools and per-call scratch, and
# the threaded-cohort / thread-scaling tests drive real concurrent
# native calls, which is exactly what TSan instruments. Reuses the
# GOLEFT_TPU_ASAN_LIB override (it just points native.py at a
# sanitizer build; the sanitizer flavor is the build's concern).
test-native-tsan: build/libgoleftio_tsan.so
	GOLEFT_TPU_ASAN_LIB=$(CURDIR)/build/libgoleftio_tsan.so \
	LD_PRELOAD=$(shell $(CXX) -print-file-name=libtsan.so) \
	TSAN_OPTIONS=report_bugs=1:halt_on_error=1 \
	python -m pytest tests/ -q -m native_io

clean:
	rm -rf build
