"""Tiny cells for the CPU tests: new files in a temporary copy of
``bench/``, found by name like any other cell."""
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BENCH_JSON = {
    "end_to_end": [
        {"name": "train_tokens_per_s", "unit": "tokens/s"},
        {"name": "tpot_p50_ms", "unit": "ms"},
        {"name": "serve_tokens_per_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def make_bench(tmp):
    """A copy of ``bench/`` with two tiny cells and a CPU row of peaks."""
    root = os.path.join(str(tmp), "bench")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "testdata", "__pycache__"))
    with open(os.path.join(root, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["kinds"]["cpu"] = peaks["kinds"]["TPU v5 lite"]
    _dump(root, "peaks.json", peaks)

    ssm = _load(root, "configs/mamba2-780m.json")
    ssm["name"] = "tiny-ssm"
    ssm["ft"]["backend"] = "xla"
    ssm["dtype"] = "float32"          # exact on the CPU: any gap is a fault
    ssm["program"]["overrides"] = {
        "n_layers": 2, "d_model": 64, "vocab_size": 512,
        "ssm": {"state": 16, "head_dim": 16, "chunk": 32}}
    _dump(root, "configs/tiny-ssm.json", ssm)
    train = _load(root, "workloads/mamba2-train-2k.json")
    _dump(root, "mixes/tiny-train.json",
          {"name": "tiny-train", "kind": "train", "batch": 2, "seq": 64})
    train.update(name="tiny-train", config="tiny-ssm", traffic="tiny-train",
                 trace_seconds=0.5,
                 limits={"loss_gap": 1e-3, "grad_gap": 1e-2,
                         "change_gap": 1e-2, "ft_detections": 0})
    _dump(root, "workloads/tiny-train.json", train)

    dense = _load(root, "configs/phi4-mini-3.8b.json")
    dense["name"] = "tiny-dense"
    dense["ft"]["backend"] = "xla"
    dense["dtype"] = "float32"        # exact on the CPU: any gap is a fault
    dense["program"]["overrides"].update(
        n_layers=2, d_model=1024, n_heads=8, n_kv_heads=4, head_dim=128,
        d_ff=2048, vocab_size=8192)
    _dump(root, "configs/tiny-dense.json", dense)
    serve = _load(root, "workloads/phi4-serve-overload.json")
    serve.update(name="tiny-serve", config="tiny-dense", traffic="tiny-chat",
                 engine={"n_slots": 3, "max_len": 96},
                 trace_start_s=0.5, trace_seconds=0.5,
                 check={"min_tokens": 30, "max_requests": 3},
                 limits={"mean_gap": 1e-3, "widest_gap": 1e-3})
    chat = _load(root, "mixes/chat-overload.json")
    chat.update(name="tiny-chat", rate_per_s=2.0)
    chat["prompt"].update(median=8, min=4, max=32, buckets=[8, 16, 32])
    chat["output"].update(median=12, min=8, max=24)
    _dump(root, "mixes/tiny-chat.json", chat)
    _dump(root, "workloads/tiny-serve.json", serve)
    return root


def _load(root, rel):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def _dump(root, rel, obj):
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f)
