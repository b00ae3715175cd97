# Developer entry points.
#
#   make t1    — the tier-1 gate: EXACTLY the ROADMAP.md verify command
#                (via scripts/t1.sh), preceded by a marker check that the
#                ingestion and chaos tests are collected in the fast
#                ('not slow') tier — a stray @pytest.mark.slow would
#                silently drop them from the gate.
#   make chaos — the fast-tier worker-health / fault-injection suite
#                (tests/test_chaos.py, 'chaos and not slow'); the
#                slow-marked chaos slices (real injected hangs/crash-loops
#                through process actors) run with the full tier or via
#                pytest -m chaos.
#   make telemetry — the fast-tier telemetry suite (tests/test_telemetry.py:
#                histogram percentiles/merge, span rings, board
#                aggregation, record schema stability, profiler capture
#                lifecycle); the slow-marked e2e slices run with the full
#                tier.
#   make learning — the fast-tier learning-diagnostics suite
#                (tests/test_learning_diag.py: device-vs-host histogram
#                parity, dQ reference agreement, staleness stamps through
#                shm/mp/ring-wrap, NaN forensics, record schema); the
#                slow e2e slice runs with the full tier.
#   make anakin — the fast-tier on-device acting suite
#                (tests/test_anakin.py: jitted-env parity, block-layout
#                parity with the host sink, replay-state identity, the
#                fused loop, kill switch); the slow gridworld
#                learnability slice runs with the full tier.
#   make anakin-sharded — the fast-tier sharded-anakin suite
#                (tests/test_anakin_sharded.py: dp=2 replay-state
#                identity vs the per-shard sequential reference,
#                per-shard RNG independence, global ε-ladder layout,
#                relaxed mesh validation, the composed loop + per-shard
#                telemetry block, the shard_imbalance rule); the slow
#                dp=2 gridworld learnability slice runs with the full
#                tier.
#   make sentinel — the fast-tier resource/compile/alerting suite
#                (tests/test_sentinel.py: rule-engine semantics, retrace
#                detection on a shape-churning jit, board RSS
#                aggregation, resource monitor + forensics dump, record
#                schema stability); the slow chaos-driven e2e slices
#                (injected hang → actor_stall alert) run with the full
#                tier.
#   make replaydiag — the fast-tier replay-observability suite
#                (tests/test_replay_diag.py: device-vs-host leaf-histogram
#                parity, sample-count ring across wrap + batched
#                overwrite, lane stamps through the queue transports and
#                the sharded anakin path, eviction lifetimes vs a
#                sequential reference, the new alert rules, kill-switch
#                record-schema stability); the slow e2e slice (populated
#                replay_diag block, nonzero never-sampled fraction) runs
#                with the full tier.
#   make fleet — the fast-tier fleet-observability suite
#                (tests/test_fleet.py: lockstep psum-row gauge math on
#                the emulated mesh (argmax/skew, kill-switch shape
#                identity), FleetAggregator merge parity vs per-rank
#                references, the four fleet alert rules incl.
#                once-per-breach edge semantics, host-row rotation,
#                trace merge + clock alignment on the checked-in
#                two-rank fixture, sentinel host-row/alert streams,
#                record-schema stability); the slow single-controller
#                lockstep e2e + two-process loopback straggler A/B run
#                with the full tier.
#   make serve — the fast-tier policy-serving suite (tests/test_serve.py:
#                micro-batcher deadline/fill semantics, state-cache
#                lease/evict/reconnect, local-vs-server action parity,
#                transport round-trips (in-proc + shm + socket), serving
#                record schema + the serve_* alert rules, kill-switch
#                schema stability, the sharded fleet: shard routing +
#                handoff, single-server parity, kill/adopt failover,
#                grow/shrink reslice, admission shed + brownout alert,
#                membership leases); the slow e2e slice (real actors
#                through the server into the learner) and the
#                server-kill/restart chaos drill run with the full tier.
#   make elastic — the fast-tier elastic-fleet suite
#                (tests/test_elastic.py: service-vs-in-mesh replay
#                parity, spill demote/promote round-trips + the >= 2x
#                capacity geometry, lane-routing provenance, the
#                socket rung, fan-out tree topology/stamp propagation
#                incl. the quant bundle, membership
#                lease/park/adopt/handoff, elastic supervision, the
#                join/leave chaos grammar, the replay_service block +
#                three fleet alert rules, the service-routed Learner);
#                the slow churn drill (leave 25% of a running fleet,
#                re-join it, zero learner stalls) runs with the full
#                tier.
#   make service-ingest — the fast-tier batched service data-plane
#                suite (tests/test_service_ingest.py: grouped-ingest
#                bit-parity with the sequential path incl. ring wrap /
#                mid-group spill demotion / lane routing, the AOT chunk
#                plan, windowed socket cumulative acks under
#                drop_ack@every chaos injection, spilled-page priority
#                write-backs, priority-ordered async prefetch, the
#                producer pump + run_replay_producer wiring, the new
#                fleet knobs' round-trip/validation, the ingest_backlog
#                rule); the slow sample-stager parity slice runs with
#                the full tier.
#   make quant — the fast-tier quantized-inference suite
#                (tests/test_quant.py: per-channel int8 round-trip
#                bounds, greedy-action agreement vs the f32 twin,
#                publish-time bundle round-trips through both weight
#                stores with staleness stamps, serve/local/anakin
#                switching through the one shared forward, the in-graph
#                probe + quant block + quant_divergence rule,
#                kill-switch schema stability, pre-PR14 config
#                round-trip); the slow int8 learnability slice runs
#                with the full tier.
#   make costmodel — the fast-tier cost-model/roofline suite
#                (tests/test_costmodel.py: XLA cost-table extraction
#                across step factories incl. a sharded emulated-mesh
#                program, named_scope presence in lowered HLO,
#                traceparse on the checked-in miniature trace, roofline
#                report + analytic golden file, the costs gate, record
#                schema stability under the kill switch).
#   make recovery — the fast-tier crash-recovery suite
#                (tests/test_recovery.py: snapshot round-trip bit-parity
#                (service shards with/without spill, the plain in-mesh
#                cut), the atomic manifest commit + torn-payload probe,
#                SnapshotWriter latest-wins, producer reconnect +
#                unacked-tail replay across a service bounce,
#                eager-connect construction failures + the dial ladder,
#                resume determinism on both learner paths, the
#                supervisor's breaker/clean-exit/resume-chain policies,
#                checkpoint retention GC, kill-switch record-schema
#                stability + inert alert rules); the slow SIGKILL drills
#                (tools/chaos.py --kill-learner / --kill-replay-service
#                end-to-end) run with the full tier.
#   make tracing — the fast-tier cross-plane tracing suite
#                (tests/test_tracing.py: hop-stamp propagation through
#                the in-proc/shm/socket serve rungs, the experience
#                lineage stamp through ring wrap + spill
#                demote/promote + snapshot restore, the trace record
#                block, kill-switch byte-identity of records and wire
#                frames, record-schema stability).
#   make tower — the fast-tier control-tower slice of the same file
#                (tests/test_tracing.py -m tower: the TowerCollector
#                join over synthesized plane streams, the derived
#                cross-plane signals, the four tower rules, clock-
#                anchor alignment, the offline-replay CLI).
#   make quality — the fast-tier policy-quality suite
#                (tests/test_quality.py: the Q-calibration join vs a
#                per-row python reference, QualityStats interval/eval
#                aggregation, shadow scoring that never mutates live
#                serving state, the gated canary promotion round-trip
#                (stage/refuse/promote/rollback + restart persistence),
#                kill-switch record-schema stability, pre-PR20 config
#                round-trips, the three quality alert rules + their
#                tower twins); the promotion drill itself
#                (tools/chaos.py --promotion) rides the e2e bench's
#                --promotion-ab evidence cell.
#   make regress — the regression gate: tools/regress.py compares the
#                tree's E2E_* artifacts against BASELINE.json's
#                'bench' snapshot (per-metric noise tolerances) AND the
#                freshly recomputed XLA cost table against its 'costs'
#                snapshot (exact match — compute regressions fail even
#                on wall-clock-noisy hosts); exit 1 on any failure.
#   make costs — write the per-program XLA cost table to COSTS.json
#                (telemetry/costmodel.py, CPU-pinned 2-device mesh).
#   make roofline — generate the roofline report (JSON + table) into
#                ROOFLINE.json: per-component flops/bytes/arithmetic
#                intensity/%-of-peak + the serial-chain model
#                (tools/roofline.py; gate preset on CPU, reference
#                shape on TPU).

.PHONY: t1 chaos telemetry learning anakin anakin-sharded sentinel \
	replaydiag fleet serve quant elastic service-ingest costmodel \
	recovery tracing tower quality regress costs roofline \
	check-fast-markers

t1: check-fast-markers
	bash scripts/t1.sh

chaos: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q \
	    -m 'chaos and not slow' -p no:cacheprovider

telemetry: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py -q \
	    -m 'not slow' -p no:cacheprovider

learning: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_learning_diag.py -q \
	    -m 'not slow' -p no:cacheprovider

anakin: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_anakin.py -q \
	    -m 'not slow' -p no:cacheprovider

anakin-sharded: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_anakin_sharded.py -q \
	    -m 'not slow' -p no:cacheprovider

sentinel: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_sentinel.py -q \
	    -m 'not slow' -p no:cacheprovider

replaydiag: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_replay_diag.py -q \
	    -m 'not slow' -p no:cacheprovider

fleet: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q \
	    -m 'not slow' -p no:cacheprovider

serve: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_serve.py -q \
	    -m 'not slow' -p no:cacheprovider

quant: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_quant.py -q \
	    -m 'not slow' -p no:cacheprovider

elastic: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py -q \
	    -m 'not slow' -p no:cacheprovider

service-ingest: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_service_ingest.py -q \
	    -m 'not slow' -p no:cacheprovider

costmodel: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_costmodel.py -q \
	    -m 'not slow' -p no:cacheprovider

recovery: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_recovery.py -q \
	    -m 'not slow' -p no:cacheprovider

tracing: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_tracing.py -q \
	    -m 'not slow' -p no:cacheprovider

tower: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_tracing.py -q \
	    -m 'tower and not slow' -p no:cacheprovider

quality: check-fast-markers
	JAX_PLATFORMS=cpu python -m pytest tests/test_quality.py -q \
	    -m 'not slow' -p no:cacheprovider

regress:
	JAX_PLATFORMS=cpu python -m r2d2_tpu.tools.regress \
	    --baseline BASELINE.json --dir .

costs:
	JAX_PLATFORMS=cpu python -m r2d2_tpu.telemetry.costmodel \
	    --out COSTS.json

roofline:
	JAX_PLATFORMS=cpu python -m r2d2_tpu.tools.roofline \
	    --out ROOFLINE.json

# One guard per suite: module:marker:min-collected:label (marker spelled
# with underscores for spaces). A stray @pytest.mark.slow (or a marker
# typo) silently drops tests from the fast tier; the count floor catches
# it.
FAST_MARKER_CHECKS := \
	tests/test_ingest.py:not_slow:10:ingestion \
	tests/test_chaos.py:chaos_and_not_slow:12:chaos \
	tests/test_telemetry.py:not_slow:20:telemetry \
	tests/test_learning_diag.py:not_slow:12:learning-diagnostics \
	tests/test_anakin.py:not_slow:10:anakin \
	tests/test_anakin_sharded.py:not_slow:8:anakin-sharded \
	tests/test_sentinel.py:not_slow:20:sentinel \
	tests/test_replay_diag.py:not_slow:10:replay-diag \
	tests/test_fleet.py:not_slow:12:fleet \
	tests/test_serve.py:not_slow:40:serve \
	tests/test_quant.py:not_slow:14:quant \
	tests/test_elastic.py:not_slow:20:elastic \
	tests/test_service_ingest.py:not_slow:20:service-ingest \
	tests/test_costmodel.py:not_slow:10:cost-model \
	tests/test_recovery.py:not_slow:18:recovery \
	tests/test_tracing.py:not_slow:16:tracing \
	tests/test_tracing.py:tower_and_not_slow:5:tower \
	tests/test_quality.py:not_slow:14:quality

check-fast-markers:
	@for spec in $(FAST_MARKER_CHECKS); do \
	    mod=$${spec%%:*}; rest=$${spec#*:}; \
	    marker=$$(echo "$${rest%%:*}" | tr '_' ' '); rest=$${rest#*:}; \
	    min=$${rest%%:*}; label=$${rest#*:}; \
	    n=$$(JAX_PLATFORMS=cpu python -m pytest "$$mod" \
	        -m "$$marker" --collect-only -q -p no:cacheprovider 2>/dev/null \
	        | grep -c '::'); \
	    if [ "$$n" -ge "$$min" ]; then \
	        echo "fast-tier $$label tests collected: $$n"; \
	    else \
	        echo "ERROR: $$label tests missing from the '$$marker' tier ($$n collected)"; \
	        exit 1; \
	    fi; \
	done
