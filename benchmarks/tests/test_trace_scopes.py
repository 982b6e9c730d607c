"""``lib/trace_scopes.py``: the reduction on a hand-built trace gives the
per-step numbers worked out by hand; the loader reads a scope path from an
op's EVENT METADATA in a serialized ``XSpace``; and the seven readers, added
as files, are all absent from a CPU rehearsal's line."""
import json
import os

import pytest

from benchmarks.lib import trace_scopes
from conftest import BENCH, run_cell

NEW = ["train_step.target_forward_ms", "train_step.online_forward_ms",
       "train_step.backward_ms", "train_step.update_ms",
       "train_step.augment_ms", "train_step.norm_ms",
       "train_step.unscoped_share"]
T = "jit(train_step)/"
US = 1_000_000                      # picoseconds


def _trace():
    """Two whole steps of 100 us, one cut short at the end of the trace,
    and the tail of a step that began before it.  Per whole step: one op
    per phase, one with a path and no phase, two with no path."""
    def step(t0):
        at = lambda us: t0 + us * US
        return [
            ("copy.1", None, at(0), 2 * US, 0),         # -> target (next)
            ("fusion.1", T + "target_forward/BYOLNet/backbone/"
             "stage1_block1/conv1/conv_general_dilated", at(2), 20 * US,
             4_000_000),
            ("fusion.2", T + "jvp(online_forward)/BYOLNet/backbone/"
             "stage1_block1/bn1/reduce_sum", at(22), 10 * US, 0),
            ("fusion.3", T + "jvp(loss)/BYOLNet.classify/probe/classifier/"
             "dot_general", at(32), 3 * US, 0),
            ("copy-done.4", None, at(35), 5 * US, 0),  # -> backward
            ("fusion.5", T + "transpose(jvp(online_forward))/BYOLNet/"
             "backbone/stem_bn/mul", at(40), 30 * US, 0),
            ("fusion.6", T + "augment/jit(clip)/max", at(70), 4 * US, 0),
            ("convert.7", T + "convert_element_type", at(74), 1 * US, 0),
            ("fusion.8", T + "update/mul", at(75), 6 * US, 0),
            ("copy.9", None, at(81), 1 * US, 0),     # -> update (prev)
        ]
    ops = step(50 * US) + step(200 * US)
    ops += [("fusion.5", T + "transpose(jvp(online_forward))/x", 10 * US,
             30 * US, 0),                       # before the first step
            ("fusion.1", T + "target_forward/x", 352 * US, 20 * US, 0)]
    steps = [(50 * US, 100 * US), (200 * US, 100 * US),
             (350 * US, 40 * US)]                   # cut by the trace's end
    return {"ops": ops, "steps": steps}


def test_reduction_on_a_hand_built_trace():
    r = trace_scopes.reduce(_trace())
    assert r["steps"] == 2
    us = lambda x: pytest.approx(x * 1e-6)
    assert r["step_s"] == us(100)
    assert r["phase_s"] == {
        "target_forward": us(22), "online_forward": us(13),
        "backward": us(35), "augment": us(4), "unscoped": us(1),
        "update": us(7)}
    assert r["op_s"] == us(82)
    assert r["inherited_s"] == {"target_forward": us(2), "backward": us(5),
                                "update": us(1)}
    # rooted in a normalisation module: bn1 forward, stem_bn backward
    assert r["norm_s"] == {"online_forward": us(10), "backward": us(30)}
    assert r["module_s"][("target_forward", "stage1")] == us(20)
    assert r["module_s"][("online_forward", "stage1")] == us(10)
    assert r["module_s"][("online_forward", "probe")] == us(3)
    assert r["module_s"][("backward", "stem")] == us(30)
    assert r["module_s"][("backward", "(no path)")] == us(5)
    assert r["module_s"][("update", "other")] == us(6)
    assert r["flops"]["target_forward"] == pytest.approx(4e6)
    assert "target_forward" in trace_scopes.table(r)
    empty = trace_scopes.reduce({"ops": _trace()["ops"], "steps": []})
    assert empty["steps"] == 0 and empty["phase_s"] == {}
    assert "no whole" in trace_scopes.table(empty)


@pytest.mark.parametrize("path,phase,module,norm", [
    (T + "target_forward/BYOLNet/backbone/block11/mlp/fc2/dot_general",
     "target_forward", "blocks", False),
    (T + "jvp(online_forward)/BYOLNet/backbone/block3/ln1/reduce_sum",
     "online_forward", "blocks", True),
    (T + "jvp(loss)/jit(take_along_axis)/gather", "online_forward", "other",
     False),
    (T + "transpose(jvp(online_forward))/BYOLNet/backbone/stage3_block1/"
     "downsample_bn/reduce_sum", "backward", "stage3", True),
    (T + "transpose(jvp(loss))/BYOLNet.classify/probe/classifier/"
     "dot_general", "backward", "probe", False),
    (T + "while/body/closed_call/update/add", "update", "other", False),
    (T + "jvp(online_forward)/BYOLNet/backbone/ln_final/mul",
     "online_forward", "backbone", True),
    (T + "target_forward/BYOLNet/projector/bn/rsqrt", "target_forward",
     "projector", True),
    (T + "target_forward/BYOLNet/backbone/patch_embed/conv_general_dilated",
     "target_forward", "patch_embed", False),
    (T + "augment/jit(clip)/max", "augment", "other", False),
    (T + "convert_element_type", "unscoped", "other", False),
    (None, None, "other", False),
])
def test_phase_module_and_norm_of_a_path(path, phase, module, norm):
    assert trace_scopes.phase_of(path) == phase
    assert trace_scopes.module_of(path) == module
    assert trace_scopes.in_norm(path) is norm


XSPACE = '''
planes { name: "/host:CPU" }
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules"
    events { metadata_id: 9 offset_ps: 1000 duration_ps: 5000 } }
  lines { name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 1000 duration_ps: 300
             stats { metadata_id: 3 uint64_value: 7 } }
    events { metadata_id: 2 offset_ps: 1300 duration_ps: 200 }
    events { metadata_id: 3 offset_ps: 1000 duration_ps: 5000 } }
  lines { name: "Async XLA Ops"
    events { metadata_id: 2 offset_ps: 1000 duration_ps: 9000 } }
  event_metadata { key: 1 value { id: 1 display_name: "fusion.1"
    name: "%fusion.1 = f32[8]{0} fusion(f32[8] %p), kind=kLoop"
    stats { metadata_id: 1
            str_value: "jit(train_step)/update/BYOLNet/add:" }
    stats { metadata_id: 2 str_value: "loop fusion" }
    stats { metadata_id: 4 int64_value: 64 } } }
  event_metadata { key: 2 value { id: 2
    name: "%copy.2 = f32[8]{0} copy(f32[8] %q)"
    stats { metadata_id: 2 str_value: "data formatting" } } }
  event_metadata { key: 3 value { id: 3 name: "%while.3 = () while()"
    stats { metadata_id: 2 str_value: "while" } } }
  event_metadata { key: 9 value { id: 9 name: "jit_train_step(123)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "hlo_category" } }
  stat_metadata { key: 3 value { id: 3 name: "device_offset_ps" } }
  stat_metadata { key: 4 value { id: 4 name: "flops" } }
}
'''


def test_load_reads_the_path_from_the_event_metadata(tmp_path):
    from jax.profiler import ProfileData
    folder = tmp_path / "plugins" / "profile" / "2026_01_01"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert trace_scopes.find_xplane(str(tmp_path)) == str(path)
    trace = trace_scopes.load(str(path))
    # the while op only contains the others; the asynchronous line is not
    # read; the trailing colon of the path is dropped
    assert trace == {
        "ops": [("fusion.1", "jit(train_step)/update/BYOLNet/add", 1000, 300,
                 64), ("copy.2", None, 1300, 200, 0)],
        "steps": [(1000, 5000)]}
    r = trace_scopes.reduced_file(str(path))
    assert trace_scopes.reduced_file(str(path)) is r      # parsed once
    assert r["phase_s"] == {"update": pytest.approx(500e-12)}
    assert r["inherited_s"] == {"update": pytest.approx(200e-12)}
    with pytest.raises(FileNotFoundError):
        trace_scopes.find_xplane(str(tmp_path / "nothing"))


def test_the_seven_readers_are_files_and_absent_off_the_chip(bench_copy):
    folder = os.path.join(bench_copy, "benchmarks", "layer_metrics")
    assert all(os.path.exists(os.path.join(folder, n + ".py")) for n in NEW)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    # augment_ms has a reader and no entry: no cell augments in the step yet
    assert set(NEW) - set(entries) == {"train_step.augment_ms"}
    rc, out, err = run_cell(bench_copy, "tiny_train", trace=1)
    assert rc == 0, err[-2000:]
    line = json.loads(out[-1])
    assert line["correct"] is True
    assert line["metrics"] and not set(line["metrics"]) & set(NEW)
