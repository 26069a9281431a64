"""Assert the BENCH_serve.json schema (CI serve-suite job).

The BENCH_serve.json counterpart of check_decode_schema.py: bench
regressions must fail loudly instead of silently renaming or dropping
keys — downstream consumers (ROADMAP claims, docs/serving.md, the v2
request-API acceptance gate on host-transfer bytes/step) read these keys
by name. Two checks:

  1. the committed repo-root BENCH_serve.json parses and carries every
     required key (stale-artifact guard);
  2. with --regen, a fresh small-trace run of serve_bench.run (written
     to a temp dir, never clobbering the committed artifact) satisfies
     the same schema (code-drift guard).

  PYTHONPATH=src python benchmarks/check_serve_schema.py [--regen]
"""
import argparse
import json
import os
import sys
import tempfile

TOP_KEYS = (
    "config", "n_requests", "n_slots",
    "static", "continuous", "continuous_int8",
    "throughput_speedup", "int8_tokens_per_s_delta",
    "kv_bytes_per_token_by_dtype", "host_transfer_bytes_per_step",
    "shared_prefix", "speculative", "paged",
)
RUN_KEYS = ("name", "tokens_per_s", "ms_per_token_p50",
            "ms_per_token_p99", "makespan_s")
CONTINUOUS_KEYS = RUN_KEYS + ("prefill_tokens", "decode_tokens",
                              "fused_steps",
                              "prefix_hits", "hit_rate",
                              "prefill_tokens_saved",
                              "prefill_tokens_saved_frac",
                              "spec_rounds", "spec_drafted",
                              "spec_accepted", "spec_k_sum")
KV_DTYPES = ("auto", "bf16", "int8", "fp8")
HOST_TRANSFER_KEYS = ("v1_logits_rows", "v2_sampled_ids",
                      "v2_with_logprobs")
SHARED_PREFIX_KEYS = ("sys_len", "no_prefix_cache", "prefix_cache",
                      "hit_rate", "prefill_tokens_saved",
                      "prefill_tokens_saved_frac", "prefix_speedup")
SPECULATIVE_KEYS = ("config", "n_slots", "draft_layers", "non_spec",
                    "spec", "spec_rounds", "accept_rate", "mean_k",
                    "tokens_per_s", "spec_speedup", "bytes_model")
BYTES_MODEL_KEYS = ("draft_step_bytes", "verify_chunk_bytes",
                    "round_bytes", "tokens_per_round",
                    "spec_bytes_per_token", "baseline_bytes_per_token",
                    "bytes_speedup")
PAGED_KEYS = ("n_requests", "n_slots", "page_size", "n_pages",
              "n_full_slots", "paged_run", "contiguous_equal_mem",
              "concurrency_peak", "pages_in_use_peak", "page_share_rate",
              "alias_acquisitions", "fresh_acquisitions", "spills",
              "restores", "paged_speedup")


def check(path: str) -> None:
    with open(path) as f:
        payload = json.load(f)
    missing = [k for k in TOP_KEYS if k not in payload]
    assert not missing, f"{path}: missing top-level keys {missing}"
    for run, keys in (("static", RUN_KEYS),
                      ("continuous", CONTINUOUS_KEYS),
                      ("continuous_int8", CONTINUOUS_KEYS)):
        missing = [k for k in keys if k not in payload[run]]
        assert not missing, f"{path}: {run} missing keys {missing}"
    bpt = payload["kv_bytes_per_token_by_dtype"]
    assert set(bpt) == set(KV_DTYPES), \
        f"{path}: kv bytes model covers {sorted(bpt)}, want {KV_DTYPES}"
    hx = payload["host_transfer_bytes_per_step"]
    missing = [k for k in HOST_TRANSFER_KEYS if k not in hx]
    assert not missing, f"{path}: host_transfer missing keys {missing}"
    # the v2 hot-path contract: decode steps ship (B,) sampled ids, not
    # a (B, V) logits block — the recorded before/after must reflect it
    assert hx["v2_sampled_ids"] < hx["v1_logits_rows"], \
        f"{path}: v2 per-step host bytes not below the v1 logits rows"
    assert hx["v2_sampled_ids"] == payload["n_slots"] * 4, \
        f"{path}: v2 bytes/step should be 4 bytes per slot (int32 ids)"
    # prefix caching on the shared-system-prompt trace: both runs carry
    # the continuous run schema; the hit fields are deterministic by
    # trace construction (every request shares the warmed sys prompt),
    # so hit_rate / tokens-saved are hard-gated — only the measured
    # speedup is timing-dependent and merely required to be present
    sp = payload["shared_prefix"]
    missing = [k for k in SHARED_PREFIX_KEYS if k not in sp]
    assert not missing, f"{path}: shared_prefix missing keys {missing}"
    for run in ("no_prefix_cache", "prefix_cache"):
        missing = [k for k in CONTINUOUS_KEYS if k not in sp[run]]
        assert not missing, \
            f"{path}: shared_prefix[{run}] missing keys {missing}"
    assert sp["no_prefix_cache"]["prefix_hits"] == 0, \
        f"{path}: the prefix_cache=False run cannot record hits"
    assert 0.5 <= sp["hit_rate"] <= 1.0, \
        f"{path}: shared-trace hit_rate {sp['hit_rate']} out of range"
    assert 0.8 <= sp["prefill_tokens_saved_frac"] <= 1.0, \
        f"{path}: expected >=80% prefill tokens saved on the shared " \
        f"trace, got {sp['prefill_tokens_saved_frac']:.2f}"
    assert sp["prefix_speedup"] > 0, f"{path}: bad prefix_speedup"
    # self-speculative decoding on the single-stream run: the round
    # counters are deterministic enough to hard-gate (rounds ran, every
    # drafted token was counted, the rule's accept rate is a
    # probability); the measured tokens/s speedup is timing-dependent
    # and only gated > 0
    sv = payload["speculative"]
    missing = [k for k in SPECULATIVE_KEYS if k not in sv]
    assert not missing, f"{path}: speculative missing keys {missing}"
    assert sv["n_slots"] == 1, \
        f"{path}: the speculative comparison must be single-stream " \
        f"(latency-bound) — multi-slot Poisson traces are arrival-bound"
    for run in ("non_spec", "spec"):
        missing = [k for k in CONTINUOUS_KEYS if k not in sv[run]]
        assert not missing, \
            f"{path}: speculative[{run}] missing keys {missing}"
    assert sv["non_spec"]["spec_rounds"] == 0, \
        f"{path}: the speculative=False run cannot record spec rounds"
    assert sv["spec_rounds"] > 0, \
        f"{path}: the speculative run never entered a draft/verify round"
    assert sv["spec"]["spec_drafted"] >= sv["spec"]["spec_accepted"] >= 0
    assert 0.0 <= sv["accept_rate"] <= 1.0, \
        f"{path}: accept_rate {sv['accept_rate']} out of [0, 1]"
    assert sv["mean_k"] >= 1.0, f"{path}: mean_k {sv['mean_k']} < 1"
    assert sv["tokens_per_s"] > 0 and sv["spec_speedup"] > 0, \
        f"{path}: bad speculative throughput fields"
    missing = [k for k in BYTES_MODEL_KEYS if k not in sv["bytes_model"]]
    assert not missing, f"{path}: bytes_model missing keys {missing}"
    assert sv["bytes_model"]["bytes_speedup"] > 0
    # paged KV cache on the over-commit burst: the pool holds only
    # n_full_slots full-length requests' worth of KV, so the paged
    # engine exceeding that concurrency is the layout's acceptance gate
    # (deterministic by burst construction — short shared-prefix
    # requests reserve few pages each); the occupancy/share counters
    # are hard-bounded and only the measured speedup is timing-dependent
    pg = payload["paged"]
    missing = [k for k in PAGED_KEYS if k not in pg]
    assert not missing, f"{path}: paged missing keys {missing}"
    for run in ("paged_run", "contiguous_equal_mem"):
        missing = [k for k in RUN_KEYS if k not in pg[run]]
        assert not missing, f"{path}: paged[{run}] missing keys {missing}"
    assert 0 < pg["n_full_slots"] < pg["n_slots"], \
        f"{path}: the paged burst must over-commit slots against the " \
        f"pool (n_full_slots={pg['n_full_slots']} vs " \
        f"n_slots={pg['n_slots']})"
    assert pg["concurrency_peak"] > pg["n_full_slots"], \
        f"{path}: paged run never exceeded the contiguous slot count " \
        f"({pg['concurrency_peak']} <= {pg['n_full_slots']}) — the " \
        f"over-commit layout bought nothing"
    assert 0 < pg["pages_in_use_peak"] <= pg["n_pages"], \
        f"{path}: pages_in_use_peak {pg['pages_in_use_peak']} outside " \
        f"(0, n_pages={pg['n_pages']}]"
    assert 0.0 <= pg["page_share_rate"] <= 1.0, \
        f"{path}: page_share_rate {pg['page_share_rate']} out of [0, 1]"
    assert pg["alias_acquisitions"] > 0, \
        f"{path}: shared-prefix burst recorded no page aliasing"
    assert pg["paged_run"]["tokens_per_s"] > 0 and pg["paged_speedup"] > 0
    print(f"ok: {path}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true",
                    help="also regenerate a small-trace artifact in a "
                         "temp dir and schema-check it")
    args = ap.parse_args()
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    check(os.path.join(root, "BENCH_serve.json"))
    if args.regen:
        if root not in sys.path:          # `python benchmarks/...` direct
            sys.path.insert(0, root)
        from benchmarks.serve_bench import run
        with tempfile.TemporaryDirectory() as td:
            run(outdir=td, n_requests=4)
            check(os.path.join(td, "BENCH_serve.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
