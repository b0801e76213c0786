"""Flagship-line contract (ISSUE 5 satellite, round-5 verdict): the
bench's FINAL stdout line must always be compact enough that a
2,000-char tail window captures every flagship field — verbose notes
and dict sidecars ride a separate `sidecars_for` line printed before
it.  Plus what every record must say about where it ran: platform,
device kind, device count, the trace source — and that a number from
the CPU never goes under a device metric's name."""
import importlib.util
import json
import os
import sys

import pytest


@pytest.fixture(scope="module")
def bench():
    """Import bench.py as a module (no jax work happens at import)."""
    path = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")
    spec = importlib.util.spec_from_file_location("bench_mod", path)
    mod = importlib.util.module_from_spec(spec)
    saved = sys.modules.get("bench_mod")
    sys.modules["bench_mod"] = mod
    spec.loader.exec_module(mod)
    yield mod
    if saved is not None:
        sys.modules["bench_mod"] = saved
    else:
        sys.modules.pop("bench_mod", None)


def _fat_checkpoint():
    """A checkpoint dict with every field populated and the sidecars
    deliberately bloated (the round-5 failure mode)."""
    fat_metrics = {
        f"fleet.counter_{i}": {"value": i * 1000, "labels": {"family": "text"}}
        for i in range(60)
    }
    return dict(
        value=5.9e6,
        metric="ops_merged_per_sec_per_chip (test)",
        unit="ops/s",
        platform="tpu",
        device_kind="TPU v5 lite",
        device_count=1,
        trace_source={"trace": "synthetic", "seed": 10514481,
                      "patches": 259778},
        kernel="pallas",
        place_algo="sort",
        last_phase="done",
        elapsed_s=600.0,
        xla_rank_value=4200000,
        xla_flight_median=4300000,
        pallas_flight_median=5900000,
        merge_latency_ms_p50=80.1,
        merge_latency_ms_p99=120.9,
        merge_latency_ms_max=200.0,
        latency_samples=1024,
        latency_note="x" * 400,
        ring_tokens_per_doc=20000,
        rank_rounds=15,
        gather_rows_per_sec=90_000_000,
        hbm_bytes_per_op_model=12.3,
        achieved_hbm_gbps_model=400.5,
        hbm_frac_model=0.49,
        roofline_note="y" * 500,
        rank_ms_measured=55.5,
        place_ms_measured=1.2,
        gather_rows_per_sec_measured=88_000_000,
        achieved_hbm_gbps_measured=390.0,
        hbm_frac=0.48,
        roofline_measured_note="z" * 500,
        e2e_value=1_200_000,
        e2e_unit="ops/s (payload decode -> SoA -> upload -> merge)",
        e2e_vs_baseline=0.6,
        e2e_note="w" * 300,
        resident_rows_per_sec=1_000_000,
        resident_rows_per_sec_best=1_100_000,
        resident_note="n" * 400,
        resident_sync_rows_per_sec=300_000,
        resident_pipeline_rows_per_sec=500_000,
        resident_pipeline_speedup=1.67,
        resident_pipeline_note="p" * 400,
        pipeline={"rounds": 48, "groups": 6, "overlap_fraction": 0.4,
                  "stage_s": 1.0, "commit_s": 0.5, "note": "q" * 200},
        rank_gather_reduction=2.57,
        rank_gather_rows_per_op=2.25,
        rank={"algo_base": "xla:wyllie", "algo_new": "xla:coalesced",
              "ring_tokens": 8194, "n_runs_max": 5010, "mean_run": 1.8,
              "ring_budget": 5248, "gather_rows_base": 458864,
              "gather_rows_new": 178537, "gather_rows_base_per_op": 5.79,
              "gather_rows_new_per_op": 2.25, "model_rows_base": 458864,
              "model_rows_new": 320224, "rank_ms_base": 6.9,
              "rank_ms_new": 11.6, "gather_rows_per_sec_base": 66168692,
              "gather_rows_per_sec_new": 15363933, "note": "g" * 300},
        resident_durable_rows_per_sec=90_000,
        resident_durable_replayed_rounds=2,
        resident_durable_fsyncs=11,
        resident_durable_group_fsyncs=4,
        resident_durable_group_rows_per_sec=120_000,
        resident_durable_note="d" * 400,
        richtext_value=2_000_000,
        richtext_unit="ops/s (concurrent marks+edits merge)",
        richtext_vs_baseline=1.0,
        sync_sessions=16,
        sync_pushes_per_sec=90.4,
        sync_push_to_visible_ms_p50=47.7,
        sync_push_to_visible_ms_p99=952.7,
        trace={"stages": {
                   "queue_wait": {"count": 104, "mean_ms": 0.4,
                                  "exemplar": "p1a2b-3f"},
                   "coalesce_wait": {"count": 104, "mean_ms": 1.1},
                   "stage": {"count": 104, "mean_ms": 12.9},
                   "commit": {"count": 104, "mean_ms": 30.1},
                   "fsync": {"count": 104, "mean_ms": 2.2},
                   "fanout": {"count": 104, "mean_ms": 1.0,
                              "exemplar": "p1a2b-68"}},
               "stage_sum_mean_ms": 47.7, "p2v_mean_ms": 47.7,
               "flight_recorded": 4096, "flight_capacity": 1024,
               "note": "x" * 300},
        sync={"pushes": 104, "batches": 14, "max_batch": 13,
              "queue_bound": 128, "max_queue_seen": 13,
              "backpressure_waits": 0, "sessions": 16, "rounds": 26,
              "committed_epoch": 50, "pipeline": True, "docs": 8,
              "epochs": 6, "push_to_visible_ms_p50": 47.7,
              "push_to_visible_ms_p99": 952.7, "pull_bytes_mean": 272.1,
              "pulls": 96, "note": "s" * 300},
        sync_readers=64,
        sync_pulls_per_sec=5200.0,
        sync_pulls_per_sec_oracle=1900.0,
        sync_read_speedup=2.74,
        sync_pull_ms_p50=3.2,
        sync_pull_ms_p99=21.5,
        readplane={"readers": 64, "docs": 4, "epochs": 4,
                   "device_pulls_per_sec": 5200.0,
                   "oracle_pulls_per_sec": 1900.0,
                   "oracle_pull_ms_p50": 8.8, "oracle_pull_ms_p99": 44.1,
                   "readbatch": {"pulls": 1024, "windows": 18,
                                 "max_window": 64, "frames": 70,
                                 "frames_shared": 954,
                                 "degraded_windows": 0, "degraded_pulls": 0,
                                 "rows": 800, "capacity": 1024,
                                 "launches": 18, "rows_fed": 800},
                   "note": "v" * 300},
        tier_hit_rate=0.91,
        tier_revive_ms_p50=2.1,
        tier_revive_ms_p99=14.7,
        tier_rows_per_sec=850_000,
        tier_all_hot_rows_per_sec=940_000,
        tier_vs_all_hot=0.9,
        tier_hot_path_ratio=0.97,
        tier={"hot_slots": 4, "docs": 32, "hits": 30, "misses": 6,
              "hit_rate": 0.91, "promotions": 6, "evictions": 2,
              "demotions": 0, "cold_revives": 0, "revive_ms_p50": 2.1,
              "revive_ms_p99": 14.7, "hot": 4, "warm": 28, "cold": 0,
              "rows_per_round": 96, "skew": "85/15 over 4-doc core",
              "rows_per_sec_all_hot": 940_000,
              "rows_per_sec_tiered": 850_000, "note": "t" * 300},
        health_tick_ns=188_000,
        health_skew_ratio=2.59,
        health={"ticks": 201, "tick_ns_p50": 180_000,
                "tick_ns_p99": 420_000, "verdict": "ok",
                "open_alerts": 0, "tracked_docs": 24, "n_shards": 4,
                "skew_ratio": 2.59,
                "docs_top": [{"doc": 0, "heat": 309.7, "per_s": 7.2,
                              "push": 309.7, "pull": 0.0, "touch": 0.0}],
                "revive_per_s": 0.0, "launches_during_ticks": 0,
                "note": "e" * 300},
        net_connections=64,
        net_pushes_per_sec=310.5,
        net_push_to_visible_ms_p50=18.3,
        net_push_to_visible_ms_p99=96.2,
        net={"connections": 64, "docs": 8, "epochs": 4, "pushes": 256,
             "pushes_per_sec": 310.5,
             "push_to_ack_ms_p50_server": 12.1,
             "push_to_ack_ms_p99_server": 80.4,
             "net_stages": {"net.ack": {"count": 256, "mean_ms": 0.3},
                            "net.send": {"count": 256, "mean_ms": 0.1}},
             "server": {"addr": "127.0.0.1:4242", "connections": 64,
                        "accepted": 64, "refused": 0, "frame_errors": 0,
                        "resumes": 0, "max_frame": 8388608,
                        "max_connections": 72},
             "note": "n" * 300},
        repl_readers=32,
        repl_pulls_per_sec=1495.2,
        repl_pulls_per_sec_leader_only=749.5,
        repl_read_scaling_x=1.99,
        repl_lag_ms_p50=34.7,
        repl_lag_ms_p99=51.4,
        repl_promotion_downtime_ms=22.9,
        repl={"readers": 32, "docs": 4, "epochs": 6, "warm_epochs": 1,
              "leader_pulls_per_sec": 749.5,
              "aggregate_pulls_per_sec": 1495.2,
              "lag_ms_p50": 34.7, "lag_ms_p99": 51.4,
              "promotion_downtime_ms": 22.9,
              "follower": {"follower_id": "bench-child",
                           "applied_epoch": 14, "lag_epochs": 0,
                           "rounds_applied": 12, "torn_tails": 0},
              "note": "f" * 300},
        shard_count=8,
        shard_rows_per_sec=900_000,
        shard_scaling_x=2.4,
        shard={"shards": 8, "rounds": 24, "groups": 12,
               "coalesced_rounds": 20, "max_group": 8,
               "backpressure_waits": 0, "stage_s": 1.2, "commit_s": 0.9,
               "overlap_s": 0.5, "docs": 32, "rows_per_round": 192,
               "rows_per_sec_1shard": 380_000, "rows_per_sec": 900_000,
               "scaling_x": 2.4, "scaling_efficiency": 0.3,
               "note": "h" * 300},
        metrics=fat_metrics,
        resilience={"launches": 100, "retries": 2, "failures": 0,
                    "note": "r" * 300},
    )


class TestFlagshipLine:
    def test_final_line_parses_and_fits_budget(self, bench):
        rec = bench.assemble_record(_fat_checkpoint())
        flag, side = bench.split_record(rec)
        line = json.dumps(flag)
        # the budget a tail window is guaranteed to capture whole
        assert len(line) <= bench.FLAGSHIP_BUDGET, len(line)
        back = json.loads(line)  # parses standalone
        # flagship numerics survive the split
        for k in ("metric", "value", "unit", "vs_baseline", "platform",
                  "device_kind", "device_count", "trace_source",
                  "resident_pipeline_speedup", "resident_durable_fsyncs",
                  "resident_durable_group_fsyncs", "rank_gather_reduction",
                  "sync_sessions", "sync_pushes_per_sec",
                  "sync_push_to_visible_ms_p50",
                  "sync_push_to_visible_ms_p99",
                  "sync_readers", "sync_pulls_per_sec",
                  "sync_pulls_per_sec_oracle", "sync_read_speedup",
                  "sync_pull_ms_p50", "sync_pull_ms_p99",
                  "shard_count", "shard_scaling_x", "shard_rows_per_sec",
                  "tier_hit_rate", "tier_revive_ms_p50",
                  "tier_revive_ms_p99", "tier_vs_all_hot",
                  "tier_hot_path_ratio",
                  "health_tick_ns", "health_skew_ratio",
                  "repl_readers", "repl_pulls_per_sec",
                  "repl_pulls_per_sec_leader_only", "repl_read_scaling_x",
                  "repl_lag_ms_p50", "repl_lag_ms_p99",
                  "repl_promotion_downtime_ms",
                  "net_connections", "net_pushes_per_sec",
                  "net_push_to_visible_ms_p50",
                  "net_push_to_visible_ms_p99"):
            assert k in back, k
        # verbose prose + dict sidecars moved to the secondary line
        assert side is not None
        for k in ("metrics", "resilience", "pipeline", "rank", "sync",
                  "shard", "tier", "health", "readplane", "repl",
                  "trace", "net",
                  "baseline_note", "roofline_note",
                  "resident_pipeline_note"):
            assert k in side, k
            assert k not in back, k
        assert side["sidecars_for"] == back["metric"]
        assert back["sidecars"] == "previous_line"

    def test_emit_order_flagship_last(self, bench, capsys):
        bench.emit_record(bench.assemble_record(_fat_checkpoint()))
        out = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
        assert len(out) == 2
        assert "sidecars_for" in json.loads(out[0])
        last = json.loads(out[-1])
        assert "metric" in last and "value" in last
        # the whole point: the LAST 2000 chars contain the full line
        tail = "\n".join(out)[-2000:]
        assert json.loads(tail.splitlines()[-1]) == last

    def test_small_record_stays_single_line(self, bench, capsys):
        bench.emit_record({"metric": "m", "value": 1, "unit": "ops/s",
                           "vs_baseline": 0.5})
        out = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
        assert len(out) == 1
        assert json.loads(out[0])["metric"] == "m"

    def test_over_budget_numerics_spill_not_core(self, bench):
        rec = {"metric": "m", "value": 1, "unit": "ops/s",
               "vs_baseline": 0.5}
        for i in range(300):
            rec[f"extra_field_{i:03d}"] = i * 1.5
        flag, side = bench.split_record(rec)
        assert len(json.dumps(flag)) <= bench.FLAGSHIP_BUDGET
        for k in ("metric", "value", "unit", "vs_baseline"):
            assert k in flag
        spilled = [k for k in side if k.startswith("extra_field_")]
        assert spilled  # the overflow went to the sidecar line


class TestWhereItRan:
    def test_tpu_record_keeps_the_device_metric_name(self, bench):
        rec = bench.assemble_record(_fat_checkpoint())
        assert rec["metric"] == "ops_merged_per_sec_per_chip (test)"
        assert (rec["platform"], rec["device_kind"], rec["device_count"]) == (
            "tpu", "TPU v5 lite", 1)

    @pytest.mark.parametrize("platform", ["cpu", "gpu", "unknown"])
    def test_off_chip_number_never_goes_under_a_device_metric_name(
            self, bench, platform):
        ck = dict(_fat_checkpoint(), platform=platform, device_kind=platform)
        if platform == "unknown":
            del ck["platform"]  # a record banked before device contact
        metric = bench.assemble_record(ck)["metric"]
        assert not metric.startswith("ops_merged_per_sec_per_chip")
        assert "not a device metric" in metric and platform in metric

    def test_no_tpu_is_an_error_unless_the_cpu_was_asked_for(
            self, bench, monkeypatch):
        """This process runs on the CPU backend.  Without an explicit
        JAX_PLATFORMS that is a failure (exit, no record); with it, a
        labelled rehearsal."""
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(SystemExit) as ei:
            bench.device_fields()
        assert ei.value.code not in (0, None) and "no TPU" in str(ei.value.code)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        dev = bench.device_fields()
        assert dev["platform"] == "cpu" and dev["device_count"] >= 1
        assert "device_kind" in dev

    def test_a_tpu_outside_the_peaks_table_is_an_error(self, bench, monkeypatch):
        import jax

        class FakeTpu:
            platform = "tpu"
            device_kind = "TPU v99 imaginary"

        monkeypatch.setattr(jax, "devices", lambda: [FakeTpu()])
        with pytest.raises(SystemExit) as ei:
            bench.device_fields()
        assert "DEVICE_PEAKS" in str(ei.value.code)

    def test_simple_configs_carry_device_fields(self, bench, capsys, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bench._emit_simple("lww_map ops merged/sec", 1e6)
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rec["platform"] == "cpu" and "not a device metric" in rec["metric"]
        assert rec["device_count"] >= 1 and "device_kind" in rec

    def test_the_guarded_parent_is_gone(self, bench):
        for name in ("main_guarded", "_emit_terminal_failure",
                     "_run_capture_child", "_last_json_record", "_ckpt_path",
                     "_fetch_sync", "HBM_PEAK"):
            assert not hasattr(bench, name), name
