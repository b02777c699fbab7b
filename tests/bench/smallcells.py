"""Small cells for the benchmark's CPU tests: a scratch checkout whose
``BENCHMARK.json`` names cells of a 4-layer, d=64 ranker (the program's own
sources linked in), so a whole run fits a CPU test."""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

SMALL = dict(num_hidden_layers=4, hidden_size=64, num_attention_heads=4,
             intermediate_size=128, vocab_size=512,
             max_position_embeddings=48, compress_dim=16, max_query_len=8,
             max_doc_len=40)


def small_config(base: str, name: str, split: int, **serving) -> dict:
    cfg = json.loads((REPO / "bench" / "configs" / f"{base}.json").read_text())
    cfg.update(SMALL, name=name, split_layer=split)
    cfg["kernels"] = {"attn_impl": "blocked", "compress_impl": "plain"}
    cfg["serving"] = dict(cfg["serving"], micro_batch=8, **serving)
    # this size's own limits (CPU, bf16 at d=64, 3 requests of 12 per
    # sample, eight seeds per cell): sound runs read rms_gap_ratio
    # 1.09-2.24 and max_gap_ratio 2.31-5.43, the fp8 control 9.45-24.6 and
    # 23.4-77.6; at this size int4 K/V reads 3.27-7.9 and 6.95-17.5
    cfg["check"] = {"rms_gap_ratio": 4.5, "max_gap_ratio": 12.0}
    return cfg


def small_traffic(base: str, n_docs: int) -> dict:
    spec = json.loads((REPO / "bench" / "traffic" / f"{base}.json")
                      .read_text())
    spec["corpus"].update(n_docs=n_docs, doc_len_median=20, doc_len_min=4,
                          doc_len_max=39)
    spec["queries"] = {"len_min": 2, "len_max": 5}
    spec["candidates"]["per_request"] = 12
    spec["arrivals"]["rate_per_s"] = 6.0
    spec["warmup"] = dict(spec["warmup"], requests=2, group=2)
    spec["check"] = {"requests": 3}
    return spec


def make_checkout(root: Path) -> Path:
    """A checkout with the benchmark's files, the program's sources (a
    link) and small cells: ``dense`` (split 2, fp16 reps, no cache) and
    ``paged`` (split 3 of 4, int8 K/V, paged doc cache that holds part of
    the corpus)."""
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    cfgs = {"small_dense": small_config("bert_base_l6_fp16", "small_dense",
                                        2),
            "small_paged": small_config("bert_base_l11_int8kv",
                                        "small_paged", 3, page_tokens=8,
                                        doc_cache_mb=0.25)}
    for name, cfg in cfgs.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    traffics = {"small_dense": small_traffic("docs_steady_l6", 48),
                "small_paged": small_traffic("cold_moving_l11", 96)}
    for name, spec in traffics.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(spec))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": n, "source": "small", "reduced": [],
                         "file": f"bench/configs/{n}.json", "why": "test"}
                        for n in cfgs]
    bench["workloads"] = [
        {"name": "dense", "config": "small_dense", "traffic": "small_dense",
         "chips": 1, "why": "test"},
        {"name": "paged", "config": "small_paged", "traffic": "small_paged",
         "chips": 1, "why": "test"}]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["paged"] if m["name"] == "doc_cache_hit_rate"
                              else ["dense", "paged"])
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


