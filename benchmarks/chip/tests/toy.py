"""A toy cell for CPU rehearsals: the phi3-mini configuration's file with
every size cut to a few dozen, the same hedge, a short traffic mix."""
import copy
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent


def write(tmp: pathlib.Path, *, rate: float = 6.0, sla_ms: float = 2000.0) -> dict:
    """Write the toy config and traffic under ``tmp``; returns a
    BENCHMARK.json-like dict naming one cell ``toy.chat``."""
    cfg = json.loads((CHIP / "configs" / "phi3-mini.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["name"] = "toy"
    cfg["config"].update(
        hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, vocab_size=512,
    )
    cfg["serving"]["geometry"] = {
        "max_len": 40, "prompt_width": 16, "max_steps": 24, "n_slots": 4,
        "page_size": 8, "bs_ladder": [1, 2, 4],
    }
    cfg["serving"]["admission"] = {"max_chunk": 4}
    cfg["correct"]["sample_remote"] = 3
    cfg["correct"]["sample_hedge"] = 1
    mix = json.loads((CHIP / "traffic" / "chat-university.json").read_text())
    mix.update(name="toy-chat", prompt_tokens=16, output_tokens=16, sla_ms=sla_ms)
    mix["arrivals"]["rate_rps"] = rate
    (tmp / "toy.json").write_text(json.dumps(cfg))
    (tmp / "toy-chat.json").write_text(json.dumps(mix))
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "toy", "file": str(tmp / "toy.json")}]
    bench["workloads"] = [{"name": "toy.chat", "config": "toy", "traffic": "toy-chat",
                           "chips": 1, "why": "rehearsal"}]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["toy.chat"]
    return bench
