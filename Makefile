# CI-shape runner — the Docker-suite analog (the reference image built the
# lib, ran nosetests + lua tests + mpirun end-to-end targets,
# deploy/docker/Dockerfile:93-113).
#
#   make check          lint + native build + tests + multi-chip dryrun +
#                       the CPU drills (no bench: a CPU timing is not a
#                       device metric)
#   make smoke          chip_smoke.py: the main path on the TPU this
#                       process holds, every phase checked against numpy;
#                       fails without a TPU
#   make cells          print the benchmark's command for every cell of
#                       BENCHMARK.json (benchmark/run.py, the one the
#                       driver runs on the chip; runs nothing itself:
#                       a run fails without a TPU; benchmark/README.md)
#   make lint           mvlint project-invariant static analysis (blocking
#                       in CI; docs/static_analysis.md)
#   make native         just the C++ layer (libmultiverso_tpu.so + C client)
#   make test           just the suite (8-device virtual CPU mesh)
#   make chaos          fault-injection + durability + telemetry suites,
#                       fixed seed (CHAOS_EXTRA_SPEC appends rules, e.g.
#                       corrupt mode; MV_CHAOS_ARTIFACT_DIR collects
#                       flight-recorder dumps + metrics JSONL for upload)
#   make failover       crash-point recovery + warm-standby failover smoke
#   make sharded        sharded-tier smoke: 2-shard group round-trip +
#                       one-shard-down failover (router + layout RPC +
#                       per-shard standby; docs/sharding.md)
#   make replicas       read-replica smoke: budget-bound watermark-stamped
#                       reads off a replica fleet + SIGKILL-a-replica
#                       failover drill (docs/serving.md)
#   make reshard        elastic-membership smoke: live split/merge/move
#                       under a write stream, zero acked-Add loss
#                       (MV_RESHARD_KILL=donor|recipient|recipient_early
#                       adds the participant-kill chaos drills;
#                       docs/sharding.md §8)
#   make metrics-smoke  short remote-training session; assert the metrics
#                       JSONL parses and key latency histograms are non-empty
#   make profile-smoke  sampling profiler + critical-path attribution
#                       end-to-end: wait sites show up, Control_Profile
#                       answers, attribution table is non-empty
#                       (docs/observability.md §13)
#   make dryrun         multi-chip sharding compile+execute check (CPU mesh)
#   make tiered         beyond-RAM tiered-storage smoke: cold-segment
#                       codec, admission/LRU policy, tiered-vs-plain
#                       equivalence, SIGKILL-mid-demotion recovery drill
#                       (MV_TIER_KILL=before_commit|after_commit selects
#                       one chaos arm; docs/tiered_storage.md)
#   make audit          fleet integrity plane: state digests + continuous
#                       divergence auditor, consistent cut → PITR/clone
#                       roundtrips, migration gap-resync units
#                       (MV_CUT_KILL=coordinator|shard arms the
#                       kill-mid-cut chaos drills; docs/fault_tolerance.md
#                       §8, docs/observability.md §14)
#   make autopilot      fleet-autopilot suite: policy hysteresis/cooldown,
#                       divergence interlock freeze/ack, Zipf hotspot
#                       split+replica drill with zero acked-Add loss
#                       (MV_AUTOPILOT_KILL=before|mid arms the
#                       kill-mid-action chaos drill; docs/autopilot.md)
#   make overload       overload-survival suite: deadline propagation,
#                       priority lanes + admission shedding + tenant
#                       quotas, retry budget + circuit breaker, stall
#                       gray-failure chaos, and the train-while-serve
#                       drill (docs/fault_tolerance.md §9)
#   make chargeback     per-tenant chargeback plane: tenant-resolved
#                       tracing, cost attribution + labeled exposition,
#                       burn-driven deadline tightening, and the live
#                       two-tenant drill (docs/observability.md §15)
#   make query          query-plane suite + the word2vec neighbor drill:
#                       server-side top-k pushdown over every table kind,
#                       shard merge vs single-shard oracle, replica-served
#                       queries with zero primary dispatches
#                       (docs/serving.md §8)
#   make autotune       self-tuning suite: config watch seam, live-knob
#                       re-reads, sensor fusion, rule table, the
#                       propose→step→verify→revert controller, the
#                       autotune-off bit-identity contract
#                       (docs/autotune.md)

PYTHON ?= python
CPU_ENV := JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
CHAOS_SEED ?= 7

.PHONY: check smoke cells lint chaos failover sharded replicas reshard \
	metrics-smoke profile-smoke native test dryrun tiered audit autopilot \
	overload chargeback query autotune clean

check: lint native test dryrun profile-smoke tiered audit autopilot \
	overload chargeback query autotune

smoke:
	$(PYTHON) chip_smoke.py

cells:
	@$(PYTHON) -c "import json; b = json.load(open('BENCHMARK.json')); \
	[print(*b['command'], '--workload', w['name'], '--seed', 1, \
	'--seconds', b['run_seconds'], '--trace', 0) for w in b['workloads']]"

lint:
	$(PYTHON) -m tools.mvlint

native:
	$(MAKE) -C multiverso_tpu/native
	$(MAKE) -C multiverso_tpu/native test_c_api CC=gcc
	$(MAKE) -C multiverso_tpu/native test_lua_ffi CC=gcc

test: native
	$(PYTHON) -m pytest tests/ -x -q

chaos:
	$(CPU_ENV) CHAOS_SEED=$(CHAOS_SEED) $(PYTHON) -m pytest \
		tests/test_fault.py tests/test_durable.py tests/test_obs.py \
		tests/test_obs_plane.py \
		tests/test_shm.py tests/test_apply_batch.py \
		tests/test_replica.py -q \
		-k "not crash_point and not failover" \
		-p no:cacheprovider -p no:randomly

metrics-smoke:
	$(CPU_ENV) $(PYTHON) tests/metrics_smoke.py

profile-smoke:
	$(CPU_ENV) $(PYTHON) tests/profile_smoke.py

failover:
	$(CPU_ENV) CHAOS_SEED=$(CHAOS_SEED) $(PYTHON) -m pytest \
		tests/test_durable.py -q -k "crash_point or failover" \
		-p no:cacheprovider -p no:randomly

sharded:
	$(CPU_ENV) $(PYTHON) -m pytest tests/test_shard.py -q \
		-k "shard_group or layout_rpc" \
		-p no:cacheprovider -p no:randomly

replicas:
	$(CPU_ENV) CHAOS_SEED=$(CHAOS_SEED) $(PYTHON) -m pytest \
		tests/test_replica.py -q \
		-k "staleness_property or sharded_replica or admission" \
		-p no:cacheprovider -p no:randomly

reshard:
	$(CPU_ENV) $(PYTHON) -m pytest tests/test_reshard.py -q \
		-p no:cacheprovider -p no:randomly

dryrun:
	$(CPU_ENV) $(PYTHON) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun_multichip(8): ok')"

tiered:
	$(CPU_ENV) $(PYTHON) -m pytest tests/test_tiered.py -q \
		-p no:cacheprovider -p no:randomly

audit:
	$(CPU_ENV) $(PYTHON) -m pytest tests/test_audit.py tests/test_cut.py \
		tests/test_migrate_unit.py -q \
		-p no:cacheprovider -p no:randomly

autopilot:
	$(CPU_ENV) $(PYTHON) -m pytest tests/test_autopilot.py -q \
		-p no:cacheprovider -p no:randomly

overload:
	$(CPU_ENV) $(PYTHON) -m pytest tests/test_overload.py -q \
		-p no:cacheprovider -p no:randomly

chargeback:
	$(CPU_ENV) $(PYTHON) -m pytest tests/test_chargeback.py -q \
		-p no:cacheprovider -p no:randomly

query:
	$(CPU_ENV) $(PYTHON) -m pytest tests/test_query.py -q \
		-p no:cacheprovider -p no:randomly
	$(CPU_ENV) $(PYTHON) examples/word2vec_query.py

autotune:
	$(CPU_ENV) $(PYTHON) -m pytest tests/test_autotune.py -q \
		-p no:cacheprovider -p no:randomly

clean:
	$(MAKE) -C multiverso_tpu/native clean
