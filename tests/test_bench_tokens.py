"""The benchmark's token-model cells at toy size, through the harness's own
entry point on the CPU: ``mellum2_12b_c3.round`` (windowed and full
attention through the interpreted flash kernel, a held share of experts,
the load-balancing term, AdamW, FedAvg, validation, checkpoint) and
``moonlight_16b_c3.round`` (latent attention through the same kernels at
two widths, a dense first block, a shared expert, sigmoid routing with a
bias that rides in ``batch_stats``) and
``nemotron_twotower_30b_c5.round`` (Mamba-2 mixers over the chunked scan,
attention without rotary embedding, ``relu2`` experts beside a shared one,
every layer one sublayer) come out ``correct`` against their plain
references, and the expert layer's counters reach the round records
and the result line."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
CELLS = ["mellum2_12b_c3.round", "moonlight_16b_c3.round",
         "nemotron_twotower_30b_c5.round"]


@pytest.fixture()
def run_cell(monkeypatch):
    """``benchmarks/run_cell.py`` as a module, with what it changes in the
    process put back afterwards: the tap on the step factory, the data
    directory, the compiled-step cache."""
    for p in (str(ROOT / "benchmarks"), str(ROOT)):
        monkeypatch.syspath_prepend(p)
    import run_cell as module
    from split_learning_tpu.runtime import context
    monkeypatch.setattr(context, "make_train_step",
                        context.make_train_step)
    monkeypatch.setenv("SLT_DATA_DIR", "")
    context._GLOBAL_STEP_CACHE.clear()
    yield module
    context._GLOBAL_STEP_CACHE.clear()
    for name in ("run_cell", "compare", "traffic", "program_trace",
                 "trace_reduce", "mixer_trace", "mla_trace", "ssm_trace"):
        sys.modules.pop(name, None)


@pytest.mark.parametrize("cell", CELLS)
def test_toy_rehearsal_of_the_token_cell_is_correct(run_cell, capsys, cell):
    assert run_cell.main(["--workload", cell, "--seed", "3000000019",
                          "--seconds", "1", "--trace", "1"]) == 0
    captured = capsys.readouterr()
    out = json.loads(captured.out.strip().splitlines()[-1])
    assert out["correct"] is True, captured.err[-3000:]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu"
    held = {"loss", "grad", "dparam", "fedavg", "ckpt"}
    assert held <= set(out["compared"])
    for name in held:
        c = out["compared"][name]
        assert c["value"] <= c["limit"], (name, c)
    # a CPU run gives no device number, only the program's own counts;
    # the expert layer's counter is one of them
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    counters = {m["name"] for m in spec["per_layer"]
                if m["source"] == "program_counter"}
    assert set(out["metrics"]) <= counters
    load = out["metrics"]["moe_load_max_over_mean"]
    assert load["unit"] == "ratio" and 1.0 <= load["value"] <= 4.0


def test_the_cells_files_say_what_the_program_is_given():
    """The reference's weight of the load-balancing term is the one the
    YAML hands the program, the YAML keeps every published width, and it
    is JSON as well as YAML."""
    import yaml
    from tests.conftest import bench_reference
    path = ROOT / "benchmarks" / "configs" / "mellum2_12b_c3.yaml"
    conf = yaml.safe_load(path.read_text())
    assert conf == json.loads(path.read_text())
    ref = bench_reference("mellum2_12b_c3")
    program = conf["program"]
    assert program["learning"]["moe-aux-weight"] == ref.AUX_WEIGHT
    kw = program["model-kwargs"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "num_experts_per_tok",
                "sliding_window", "rms_norm_eps", "rope_parameters"):
        assert kw[key] == conf[key] == ref.SIZES[key], key
    # the router keeps its published width; 8 experts are held
    assert kw["num_experts"] == conf["published"]["num_experts"] == 64
    assert kw["experts_held"] == conf["num_experts"] == 8
    assert kw["vocab_size"] == conf["vocab_size"] == 98304 // 8
    assert set(conf["reduced"]) >= {"num_hidden_layers", "num_experts",
                                    "vocab_size"}
    # 340.3 M parameters, as PERF.md reckons them
    import jax
    shapes = jax.eval_shape(lambda k: ref.init(k, kw)[0], jax.random.key(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) \
        == conf["held-here"]["parameters"] == 340349184
