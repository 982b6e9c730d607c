"""Unit tests for bench.py's measurement-protection machinery.

- `_flush_partial` must never destroy a pre-existing partial file (first
  flush moves it to `<path>.prev`);
- any per-config failure counts as did-not-fit (the ladder steps down);
- with no chip and no request for the CPU, `main()` exits non-zero
  before anything is measured — there is no fallback to an old number;
- the MFU accounting must follow the 2-FLOPs-per-MAC convention of the
  quoted chip peaks (an earlier ~12% figure was a 1-FLOP/MAC mismatch of
  the same measurement).
"""
import importlib.util
import json
import os

import pytest


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    """Import bench.py as a throwaway module with cwd in a temp dir."""
    monkeypatch.chdir(tmp_path)
    spec = importlib.util.spec_from_file_location(
        "bench_under_test",
        os.path.join(os.path.dirname(__file__), "..", "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestFlushPreservation:
    def test_first_flush_backs_up_existing_file(self, bench, tmp_path):
        prior = {"results": [{"config": "precious"}]}
        with open("bench_partial.json", "w") as f:
            json.dump(prior, f)
        bench._record("new_run", x=1)
        with open("bench_partial.json") as f:
            assert json.load(f)["results"][0]["config"] == "new_run"
        with open("bench_partial.json.prev") as f:
            assert json.load(f) == prior

    def test_later_flushes_do_not_rotate_again(self, bench):
        bench._record("a")
        bench._record("b")
        with open("bench_partial.json") as f:
            assert [r["config"] for r in json.load(f)["results"]] == ["a", "b"]
        assert not os.path.exists("bench_partial.json.prev")


class TestFailureClassification:
    def test_any_failure_is_logged_as_did_not_fit(self, bench, capsys):
        for err in ("RESOURCE_EXHAUSTED: out of memory",
                    "UNAVAILABLE: transient", "shape mismatch"):
            try:
                raise RuntimeError(err)
            except RuntimeError as e:
                assert bench._config_failed("ctx", e) is None
        assert capsys.readouterr().err.count("treating as did-not-fit") == 3


class TestRequiresChip:
    """No chip and no request for the CPU -> non-zero exit, nothing built,
    nothing printed on stdout (ISSUE 22: no old number replayed, no silent CPU)."""

    @pytest.mark.parametrize("argv", [[], ["--sweep"], ["--mvc"],
                                      ["--serve-ladder"]])
    def test_exits_nonzero_off_tpu(self, bench, monkeypatch, capsys, argv):
        import sys as _sys
        from byol_tpu.core import preflight
        monkeypatch.setattr(preflight, "cpu_requested", lambda: False)
        monkeypatch.setattr(bench.jax, "default_backend", lambda: "cpu")
        monkeypatch.setattr(
            bench, "_throughput",
            lambda *a, **k: pytest.fail("measured without a chip"))
        monkeypatch.setattr(_sys, "argv", ["bench.py"] + argv)
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code not in (0, None)
        assert "not 'tpu'" in str(exc.value.code)
        assert capsys.readouterr().out == ""

    def test_runs_on_cpu_when_asked(self, bench, monkeypatch, capsys):
        """JAX_PLATFORMS=cpu (the test harness) IS a request for the CPU:
        the toy configuration measures and the headline prints."""
        import sys as _sys
        measured = []
        monkeypatch.setattr(
            bench, "_throughput",
            lambda bs, *a, **k: measured.append(bs) or bench._Rate(1.0, {}))
        monkeypatch.setattr(_sys, "argv", ["bench.py"])
        bench.main()
        assert measured
        assert json.loads(capsys.readouterr().out)["value"] == 1.0

    def test_no_child_process_on_the_start_up_path(self, bench,
                                                   monkeypatch):
        """One process per chip: start-up must not spawn anything."""
        import subprocess
        import sys as _sys
        from byol_tpu.core import preflight

        def boom(*a, **k):  # pragma: no cover - must not be reached
            raise AssertionError("start-up spawned a child process")
        monkeypatch.setattr(subprocess, "run", boom)
        monkeypatch.setattr(subprocess, "Popen", boom)
        monkeypatch.setattr(preflight, "cpu_requested", lambda: False)
        monkeypatch.setattr(bench.jax, "default_backend", lambda: "cpu")
        monkeypatch.setattr(_sys, "argv", ["bench.py"])
        with pytest.raises(SystemExit):
            bench.main()


class TestSweepResume:
    """A sweep re-run after an interrupted attempt must converge: reuse
    measured rows, never re-attempt the known compile-OOM (un-rematted
    bs1024), and order the risky rematted-1024 rows last."""

    _PRIOR = {
        "device_kind": "TPU v5 lite",
        "results": [
            {"config": "sweep_bs512_remat0_fuse1", "batch_per_chip": 512,
             "fit": True, "remat": False, "fuse_views": True,
             "images_per_sec_per_chip": 709.4, "mfu": 0.235},
            {"config": "sweep_bs384_remat0_fuse1", "batch_per_chip": 384,
             "fit": False},
        ],
    }

    @staticmethod
    def _fake_tpu(bench, monkeypatch, kind="TPU v5 lite"):
        import types
        monkeypatch.setattr(
            bench.jax, "devices",
            lambda: [types.SimpleNamespace(device_kind=kind)])

    def test_prior_rows_scanned_from_live_and_prev(self, bench, monkeypatch):
        self._fake_tpu(bench, monkeypatch)
        with open("bench_partial.json.prev", "w") as f:
            json.dump(self._PRIOR, f)
        with open("bench_partial.json", "w") as f:
            json.dump({"device_kind": "TPU v5 lite", "results": [
                {"config": "tpu_first", "fit": True},            # not sweep_*
                {"config": "sweep_bs256_remat1_fuse1", "fit": True,
                 "batch_per_chip": 256, "remat": True, "fuse_views": True,
                 "images_per_sec_per_chip": 800.0, "mfu": 0.27}]}, f)
        prior = bench._sweep_prior_rows()
        assert set(prior) == {"sweep_bs512_remat0_fuse1",
                              "sweep_bs384_remat0_fuse1",
                              "sweep_bs256_remat1_fuse1"}

    def test_other_device_kind_rows_are_not_reused(self, bench, monkeypatch):
        # rows captured on a different chip generation (or the cpu
        # fallback) are incomparable — never carried into this run
        self._fake_tpu(bench, monkeypatch, kind="TPU v4")
        for kind in ("cpu", "TPU v5 lite"):
            with open("bench_partial.json", "w") as f:
                json.dump(dict(self._PRIOR, device_kind=kind), f)
            assert bench._sweep_prior_rows() == {}

    def test_resume_of_a_resumed_sweep(self, bench, monkeypatch):
        # a thrice-interrupted sweep reloads rows that were themselves
        # recorded by a resume (they carry reused=True) — must not crash
        self._fake_tpu(bench, monkeypatch)
        prior = {"device_kind": "TPU v5 lite", "results": [
            {"config": "sweep_bs512_remat0_fuse1", "batch_per_chip": 512,
             "fit": True, "remat": False, "fuse_views": True, "reused": True,
             "images_per_sec_per_chip": 709.4, "mfu": 0.235}]}
        with open("bench_partial.json", "w") as f:
            json.dump(prior, f)
        monkeypatch.setattr(bench, "_throughput",
                            lambda bs, *a, **k: 100.0)
        monkeypatch.setattr(bench.jax, "default_backend", lambda: "tpu")
        bench._sweep("resnet50", 224, [1024, 512, 256], lambda v: 0.1)
        rows = json.load(open("bench_sweep.json"))
        assert sum(r.get("images_per_sec_per_chip") == 709.4
                   for r in rows) == 1

    def test_sweep_table_rotated_not_clobbered(self, bench, monkeypatch):
        # a partial re-run must never destroy a complete prior table: the
        # existing bench_sweep.json moves to .prev before the new write
        self._fake_tpu(bench, monkeypatch)
        complete = [{"batch_per_chip": 512, "images_per_sec_per_chip": 1.0}]
        with open("bench_sweep.json", "w") as f:
            json.dump(complete, f)
        monkeypatch.setattr(bench, "_throughput", lambda bs, *a, **k: 100.0)
        monkeypatch.setattr(bench.jax, "default_backend", lambda: "tpu")
        bench._sweep("resnet50", 224, [512, 256], lambda v: 0.1)
        assert json.load(open("bench_sweep.json.prev")) == complete
        assert json.load(open("bench_sweep.json"))[0][
            "images_per_sec_per_chip"] == 100.0

    def _measure_with_prior_1024_row(self, bench, monkeypatch, row_extra):
        self._fake_tpu(bench, monkeypatch)
        with open("bench_partial.json", "w") as f:
            json.dump({"device_kind": "TPU v5 lite", "results": [
                dict({"config": "sweep_bs1024_remat1_fuse1",
                      "batch_per_chip": 1024, "fit": False}, **row_extra)]},
                      f)
        measured = []

        def fake_throughput(bs, image_size, arch, **kw):
            measured.append((bs, kw["remat"], kw["fuse_views"]))
            return 100.0
        monkeypatch.setattr(bench, "_throughput", fake_throughput)
        monkeypatch.setattr(bench.jax, "default_backend", lambda: "tpu")
        bench._sweep("resnet50", 224, [1024, 512, 256], lambda v: 0.1)
        return measured

    def test_oom_rows_at_1024_stay_reused(self, bench, monkeypatch):
        # the >=1024 compile-OOMs are the multi-minute failures —
        # fit=False rows whose recorded error carries a genuine OOM
        # signature ARE reused
        measured = self._measure_with_prior_1024_row(
            bench, monkeypatch,
            {"error": "JaxRuntimeError('INTERNAL: ... tpu_compile_helper "
                      "subprocess exit code 1')"})
        assert (1024, True, True) not in measured
        assert (1024, True, False) in measured   # distinct config still runs

    def test_transient_1024_failures_are_reattempted(self, bench,
                                                     monkeypatch):
        # a transient error must not permanently mask the one config
        # where bs1024 might fit: without
        # an OOM signature (or with no recorded error at all) re-attempt
        measured = self._measure_with_prior_1024_row(
            bench, monkeypatch, {"error": "UNAVAILABLE: Socket closed"})
        assert (1024, True, True) in measured

    def test_grid_reuses_prior_and_never_reattempts_oom_1024(
            self, bench, monkeypatch):
        self._fake_tpu(bench, monkeypatch)
        with open("bench_partial.json", "w") as f:
            json.dump(self._PRIOR, f)
        measured = []

        def fake_throughput(bs, image_size, arch, **kw):
            measured.append((bs, kw["remat"], kw["fuse_views"]))
            return 100.0
        monkeypatch.setattr(bench, "_throughput", fake_throughput)
        monkeypatch.setattr(bench.jax, "default_backend", lambda: "tpu")
        bench._sweep("resnet50", 224, [1024, 512, 256, 128, 64, 32],
                     lambda v: 0.1)
        # the measured (fit=True) row was not re-measured...
        assert (512, False, True) not in measured
        # ...but a sub-1024 fit=False row IS re-attempted: it may be a
        # mislabeled transient, and its re-measure is cheap
        assert (384, False, True) in measured
        # un-rematted 1024 never attempted; rematted 1024 attempted LAST
        assert all(remat for bs, remat, _ in measured if bs == 1024)
        assert [m for m in measured if m[0] == 1024] == measured[-2:]
        # no rung below 256 in the sweep grid
        assert min(bs for bs, _, _ in measured) >= 256
        rows = json.load(open("bench_sweep.json"))
        reused = [r for r in rows
                  if r.get("images_per_sec_per_chip") == 709.4]
        assert len(reused) == 1      # measured row carried into the table


class TestMVC:
    """--mvc (minimum-viable capture) must fit a few chip-minutes: one
    rung per headline family at the best KNOWN batch size, the rematted
    bs512 row under the sweep naming contract, and a fresh headline
    line."""

    _PRIOR = {
        "device_kind": "TPU v5 lite", "arch": "resnet50",
        "results": [
            {"config": "tpu_first", "batch_per_chip": 512, "fit": True,
             "images_per_sec_per_chip": 715.6, "mfu": 0.238},
            {"config": "tpu_first", "batch_per_chip": 256, "fit": True,
             "images_per_sec_per_chip": 776.1, "mfu": 0.258},
            {"config": "reference_faithful", "batch_per_chip": 128,
             "fit": True, "images_per_sec_per_chip": 495.7, "mfu": 0.165},
        ],
    }

    @staticmethod
    def _fake_tpu(bench, monkeypatch):
        import types
        monkeypatch.setattr(
            bench.jax, "devices",
            lambda: [types.SimpleNamespace(device_kind="TPU v5 lite")])

    def test_prior_best_rungs_prefers_fastest_fit(self, bench, monkeypatch):
        self._fake_tpu(bench, monkeypatch)
        with open("bench_partial.json", "w") as f:
            json.dump(self._PRIOR, f)
        rungs = bench._prior_best_rungs()
        # bs256 is the FASTER tpu_first rung even though 512 also fits
        assert rungs["tpu_first"] == 256
        assert rungs["reference_faithful"] == 128

    def test_other_device_kind_rungs_ignored(self, bench, monkeypatch):
        import types
        monkeypatch.setattr(
            bench.jax, "devices",
            lambda: [types.SimpleNamespace(device_kind="TPU v4")])
        with open("bench_partial.json", "w") as f:
            json.dump(self._PRIOR, f)
        assert bench._prior_best_rungs() == {}

    def _run_mvc(self, bench, monkeypatch, capsys, fail_at=()):
        self._fake_tpu(bench, monkeypatch)
        with open("bench_partial.json", "w") as f:
            json.dump(self._PRIOR, f)
        measured = []

        def fake_throughput(bs, image_size, arch, **kw):
            measured.append((bs, kw.get("remat", False),
                             kw["ema_update_mode"], kw["half"]))
            if (bs, kw.get("remat", False)) in fail_at:
                raise RuntimeError("XLA compile error")
            return 700.0
        monkeypatch.setattr(bench, "_throughput", fake_throughput)
        # main() stamps device metadata on _partial before dispatching to
        # _mvc; the sweep-reuse contract keys on it
        bench._partial.update(device_kind="TPU v5 lite", arch="resnet50")
        bench._mvc("resnet50", 224, [1024, 512, 256, 128, 64, 32], True,
                   lambda v: 0.25, "dense")
        out = json.loads(capsys.readouterr().out)
        return measured, out

    def test_one_rung_per_family_plus_remat_row(self, bench, monkeypatch,
                                                capsys):
        measured, out = self._run_mvc(bench, monkeypatch, capsys)
        # exactly one rung per family, at the prior best-known batch
        assert measured == [
            (256, False, "post", True),            # tpu_first @ prior best
            (128, False, "reference_pre", False),  # reference_faithful
            (256, False, "reference_pre", True),   # bf16 middle rung
            (512, True, "post", True),             # the rematted sweep row
        ]
        assert out["value"] == 700.0
        assert out["vs_baseline"] == 1.0
        assert out["dtype_gain"] == 1.0 and out["redesign_gain"] == 1.0
        # the remat row is recorded under the sweep naming contract, so a
        # later full --sweep reuses it (_sweep_prior_rows)
        rows = json.load(open("bench_partial.json"))["results"]
        remat = [r for r in rows
                 if r["config"] == "sweep_bs512_remat1_fuse1"]
        assert remat and remat[0]["fit"] and remat[0]["remat"] is True
        prior = bench._sweep_prior_rows()
        assert "sweep_bs512_remat1_fuse1" in prior

    def test_failed_rung_steps_down_once(self, bench, monkeypatch, capsys):
        measured, out = self._run_mvc(bench, monkeypatch, capsys,
                                      fail_at={(256, False)})
        # 256 fails for tpu_first AND bf16_ref; each steps down exactly once
        assert (128, False, "post", True) in measured
        assert (128, False, "reference_pre", True) in measured
        assert out["value"] == 700.0

    def test_headline_survives_missing_families(self, bench, monkeypatch,
                                                capsys):
        # every non-primary family failing entirely must still print a
        # fresh headline (vs_baseline null), never crash the capture
        measured, out = self._run_mvc(
            bench, monkeypatch, capsys,
            fail_at={(128, False), (64, False), (512, True)})
        assert out["value"] == 700.0
        assert out["vs_baseline"] is None and "dtype_gain" not in out


class TestKnownOOM:
    """The un-rematted rn50@224 bs1024 compile is a recorded 25+ minute
    failure — no ladder may ever re-attempt it."""

    def test_truth_table(self, bench):
        assert bench._known_oom(1024, "resnet50", 224)
        assert bench._known_oom(1024, "resnet50", 224, remat=False)
        assert not bench._known_oom(1024, "resnet50", 224, remat=True)
        assert not bench._known_oom(512, "resnet50", 224)
        assert not bench._known_oom(1024, "vit_b16", 224)   # own ladders
        assert not bench._known_oom(1024, "resnet50", 96)   # start below

    def test_headline_ladder_skips_and_records(self, bench, monkeypatch,
                                               capsys):
        import sys as _sys
        import types
        monkeypatch.setattr(
            bench.jax, "devices",
            lambda: [types.SimpleNamespace(device_kind="TPU v5 lite")])
        monkeypatch.setattr(bench.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(bench.jax.config, "update", lambda *a: None)
        monkeypatch.setattr(_sys, "argv", ["bench.py"])
        attempted = []

        def fake_throughput(bs, *a, **kw):
            attempted.append(bs)
            return 500.0
        monkeypatch.setattr(bench, "_throughput", fake_throughput)
        bench.main()
        assert 1024 not in attempted       # never compiled
        rows = json.load(open("bench_partial.json"))["results"]
        skipped = [r for r in rows if r.get("batch_per_chip") == 1024]
        assert skipped and all("documented" in r["error"] for r in skipped)
        out = json.loads(capsys.readouterr().out)
        assert out["value"] == 500.0


class TestMFUAccounting:
    def test_flops_per_sample_uses_8_forward_equivalents(self, bench):
        # 2 online + 2 target fwds + backward(2x) = 8 fwd-images, 2 FLOPs/MAC
        got = bench._flops_per_sample("resnet50", 224)
        assert got == pytest.approx(8 * 4.089e9 * 2, rel=1e-6)

    def test_unknown_shape_returns_none(self, bench):
        assert bench._flops_per_sample("resnet50", 96) is not None
        assert bench._flops_per_sample("resnet99", 224) is None


class TestArchOverride:
    """--arch (BASELINE config-5 ViT swap) must isolate its evidence file
    and carry its own FLOPs accounting."""

    def test_vit_arch_uses_own_partial_path(self, bench, monkeypatch):
        import sys as _sys
        from byol_tpu.core import preflight
        monkeypatch.setattr(_sys, "argv", ["bench.py", "--arch", "vit_b16"])
        monkeypatch.setattr(preflight, "cpu_requested", lambda: False)
        monkeypatch.setattr(bench.jax, "default_backend", lambda: "cpu")
        # no chip -> clean SystemExit, and by then the resnet partial path
        # has been swapped for the arch's own and never touched
        with pytest.raises(SystemExit, match="not 'tpu'"):
            bench.main()
        assert bench._PARTIAL_PATH == "bench_partial_vit_b16.json"
        assert not os.path.exists("bench_partial.json.prev")

    def test_vit_flops_accounting(self, bench):
        # 8 forward-image-equivalents x 17.56 GMACs x 2 FLOPs/MAC
        assert bench._flops_per_sample("vit_b16", 224) == pytest.approx(
            8 * 17.56 * 2 * 1e9)

    def test_unknown_arch_has_no_mfu(self, bench):
        assert bench._flops_per_sample("resnet200w2", 224) is None

    def test_arch_typo_fails_fast(self, bench, monkeypatch):
        import sys as _sys
        monkeypatch.setattr(_sys, "argv", ["bench.py", "--arch", "vit_b_16"])
        with pytest.raises(SystemExit, match="unknown arch"):
            bench.main()


class TestInputLadderPlumbing:
    """ISSUE 3 bench surface: every row records h2d_bytes_per_step, and the
    --input-ladder / --dry-compile plumbing carries --augment-placement."""

    def test_batch_h2d_bytes_concrete_and_abstract(self, bench):
        import numpy as np
        import jax as _jax
        concrete = {"view1": np.zeros((2, 4, 4, 3), np.float32),
                    "view2": np.zeros((2, 4, 4, 3), np.float32),
                    "label": np.zeros((2,), np.int32)}
        want = 2 * (2 * 4 * 4 * 3 * 4) + 2 * 4
        assert bench._batch_h2d_bytes(concrete) == want
        abstract = {"images": _jax.ShapeDtypeStruct((2, 4, 4, 3), np.uint8),
                    "label": _jax.ShapeDtypeStruct((2,), np.int32)}
        assert bench._batch_h2d_bytes(abstract) == 2 * 4 * 4 * 3 + 2 * 4

    def test_abstract_batch_placements(self, bench, mesh8):
        import numpy as np
        raw = bench._abstract_batch(8, 16, mesh8, augment_placement="step")
        assert sorted(raw) == ["images", "label"]
        assert raw["images"].dtype == np.uint8
        views = bench._abstract_batch(8, 16, mesh8)
        assert sorted(views) == ["label", "view1", "view2"]
        assert views["view1"].dtype == np.float32
        # the 8x H2D contract, end to end through the helper pair
        assert (bench._batch_h2d_bytes(views) - 8 * 4
                == 8 * (bench._batch_h2d_bytes(raw) - 8 * 4))

    def test_gate_args_forward_placement_and_arch(self, bench):
        args = bench._gate_args(512, 256, "dots", "average", "dense",
                                "vit_b16", placement="step")
        assert "--augment-placement" in args
        assert args[args.index("--augment-placement") + 1] == "step"
        assert args[args.index("--arch") + 1] == "vit_b16"

    def test_input_gate_phase_names_both_placements(self, bench,
                                                    monkeypatch):
        ran = []

        def fake_gates(rungs, timeout):
            ran.extend(name for name, _ in rungs)
            return {name: {"status": "ok", "row": {}} for name, _ in rungs}
        monkeypatch.setattr(bench, "_run_compile_gates", fake_gates)
        gates = bench._input_gate_phase(False, None, "dense")
        # CPU fallback ladder: one effective rung, both placements
        assert ran == ["input_eff32_mb16_loader", "input_eff32_mb16_step"]
        assert set(gates) == set(ran)
