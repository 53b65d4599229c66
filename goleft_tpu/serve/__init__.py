"""Warm-mesh coverage service: the long-running L6 layer over the stack.

Every other tool in this repo is a cold-start CLI — each invocation
pays backend bring-up, device probe and XLA compilation before the
first window of depth comes back, and concurrent users get zero
batching. The ROADMAP north star ("serving heavy traffic from millions
of users") is a service shape: this package keeps ONE process alive
with the jitted depth/indexcov/cohort programs warm and coalesces
concurrent requests into batched device passes — the same
batched-amortization argument gpuPairHMM makes for pair-HMM batching
(arxiv 2411.11547) and GenPIP for tightly integrated pipelines
(arxiv 2209.08600), applied at the request layer.

Pieces (all stdlib — no new dependencies):

  batcher.py    MicroBatcher: coalesces requests arriving within a
                window into one batch per compatible group, with
                bounded queue depth (429 on overload) and per-request
                deadlines
  executors.py  warm batch executors — a batch of depth requests runs
                as ONE vmapped device pass per shard; indexcov
                requests share one chrom_qc call per chromosome;
                cohortdepth requests concatenate into one cohort
  server.py     ThreadingHTTPServer app: /v1/{depth,indexcov,
                cohortdepth}, /healthz, /metrics, session result
                cache (parallel/scheduler.ResultCache), SIGTERM drain
  client.py     thin stdlib client (urllib) for scripts and the smokes
  metrics.py    request/batch/cache counters + latency percentiles
  smoke.py      the `make serve-smoke` end-to-end check

Entry point: ``goleft-tpu serve`` (commands/serve.py).
"""
