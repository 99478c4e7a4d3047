"""Host data-pipeline throughput benchmark.

Two modes, one JSON (BENCH_LOADER.json):
- arrow: synthetic pre-tokenized arrow shards (~256MB of uint32 tokens),
  the production path (mmap'd zero-copy slicing).
- parquet: synthetic raw-text parquet shards tokenized on the fly with a
  locally-built BPE tokenizer — the reference's ParquetHandler path
  (ref:fms_fsdp/utils/dataset_utils.py:371-457). This is compute-bound on
  the tokenizer, which is where worker parallelism matters
  (ref:dataloader_utils.py:144-146 gets it from torch worker processes;
  we get it from threaded pipeline workers — tokenizers' rust encode
  releases the GIL).

Both run the full 7-layer stateful pipeline exactly as
main_training_llama assembles it and report tokens/sec pulled on the
host against per-chip device demand.

Device demand reference points (round-2 chip runs, records deleted in
PR 21): llama3_194m_4k consumes ~65k tok/s/chip, the 7B-shaped row ~30k
tok/s/chip; an 8-chip host therefore needs ~0.5M tok/s at the 194m
rate. Pass/fail bar: host throughput >= 2x device demand per host.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pyarrow as pa


def build_dataset(root, n_files=8, docs_per_file=2000, doc_len=1000):
    schema = pa.schema([pa.field("tokens", pa.uint32())])
    os.makedirs(os.path.join(root, "dataset_1"), exist_ok=True)
    rng = np.random.default_rng(0)
    meta = []
    for f in range(n_files):
        path = os.path.join(root, "dataset_1", f"shard_{f}.arrow")
        with pa.ipc.new_file(path, schema) as w:
            for _ in range(docs_per_file):
                doc = rng.integers(0, 32000, size=doc_len, dtype=np.uint32)
                w.write(pa.record_batch([pa.array(doc)], schema))
        meta.append(
            (f"/dataset_1/shard_{f}.arrow", docs_per_file, docs_per_file * doc_len)
        )
    os.makedirs(os.path.join(root, "meta"), exist_ok=True)
    with open(os.path.join(root, "meta", "combined_counts.csv"), "w") as f:
        f.write("dataset/filename,documents,tokens\n")
        for name, d, t in meta:
            f.write(f"{name},{d},{t}\n")
    return sum(m[2] for m in meta)


# one vocabulary for BOTH the tokenizer training corpus and the parquet
# docs: if they diverge, most words tokenize to <unk> and the benchmark
# silently measures far less BPE merge work
_WORDS = [f"w{i:05d}" for i in range(4000)]


def build_tokenizer(tok_dir, vocab_size=8192):
    """Train a small BPE tokenizer offline (no hub access) and save it in
    HF AutoTokenizer layout."""
    from tokenizers import Tokenizer, models, pre_tokenizers, trainers

    os.makedirs(tok_dir, exist_ok=True)
    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    trainer = trainers.BpeTrainer(
        vocab_size=vocab_size, special_tokens=["<unk>", "<s>", "</s>"]
    )
    rng = np.random.default_rng(7)
    corpus = (
        " ".join(rng.choice(_WORDS, size=64).tolist()) for _ in range(4000)
    )
    tok.train_from_iterator(corpus, trainer)
    tok.save(os.path.join(tok_dir, "tokenizer.json"))
    with open(os.path.join(tok_dir, "tokenizer_config.json"), "w") as f:
        json.dump(
            {
                "tokenizer_class": "PreTrainedTokenizerFast",
                "bos_token": "<s>",
                "eos_token": "</s>",
                "unk_token": "<unk>",
            },
            f,
        )
    return tok_dir


def build_parquet_dataset(root, n_files=4, docs_per_file=400, words_per_doc=700):
    """Raw-text parquet shards; docs are random word sequences so the BPE
    tokenizer does real merge work per doc."""
    import pyarrow.parquet as pq

    os.makedirs(os.path.join(root, "dataset_1"), exist_ok=True)
    rng = np.random.default_rng(1)
    words = _WORDS
    meta = []
    for f in range(n_files):
        docs = [
            " ".join(rng.choice(words, size=words_per_doc).tolist())
            for _ in range(docs_per_file)
        ]
        path = os.path.join(root, "dataset_1", f"shard_{f}.parquet")
        pq.write_table(pa.table({"text": docs}), path)
        meta.append((f"/dataset_1/shard_{f}.parquet", docs_per_file))
    os.makedirs(os.path.join(root, "meta"), exist_ok=True)
    with open(os.path.join(root, "meta", "combined_counts.csv"), "w") as f:
        f.write("dataset/filename,documents,tokens\n")
        for name, d in meta:
            f.write(f"{name},{d},{d * words_per_doc}\n")


def build_mixed_dataset(root, n_files=2, docs_per_file=1000, doc_len=1000):
    """Three weighted arrow corpora for the mixed-mode row (same token
    format as build_dataset, split across corpus directories)."""
    schema = pa.schema([pa.field("tokens", pa.uint32())])
    rng = np.random.default_rng(3)
    meta = []
    for name in ("dataset_1", "dataset_2", "dataset_3"):
        os.makedirs(os.path.join(root, name), exist_ok=True)
        for f in range(n_files):
            path = os.path.join(root, name, f"shard_{f}.arrow")
            with pa.ipc.new_file(path, schema) as w:
                for _ in range(docs_per_file):
                    doc = rng.integers(0, 32000, size=doc_len, dtype=np.uint32)
                    w.write(pa.record_batch([pa.array(doc)], schema))
            meta.append(
                (f"/{name}/shard_{f}.arrow", docs_per_file,
                 docs_per_file * doc_len)
            )
    os.makedirs(os.path.join(root, "meta"), exist_ok=True)
    with open(os.path.join(root, "meta", "combined_counts.csv"), "w") as f:
        f.write("dataset/filename,documents,tokens\n")
        for name, d, t in meta:
            f.write(f"{name},{d},{t}\n")
    return sum(m[2] for m in meta)


def run_mode(mode, num_workers, n_batches, worker_mode="thread"):
    from fms_fsdp_tpu.config import TrainConfig
    from fms_fsdp_tpu.data import get_data_loader

    mix_extras = {}
    if mode == "arrow":
        root = "/tmp/bench_loader_data"
        if not os.path.exists(os.path.join(root, "meta")):
            total = build_dataset(root)
            print(f"# built {total/1e6:.0f}M tokens", file=sys.stderr)
        extra = dict(file_type="arrow", vocab_size=32000)
    elif mode == "mixed":
        root = "/tmp/bench_loader_mixed"
        if not os.path.exists(os.path.join(root, "meta")):
            total = build_mixed_dataset(root)
            print(f"# built {total/1e6:.0f}M mixed tokens", file=sys.stderr)
        extra = dict(
            file_type="arrow",
            vocab_size=32000,
            datasets="dataset_1,dataset_2,dataset_3",
            weights="2,1,1",
        )
    else:
        root = "/tmp/bench_loader_parquet"
        tok_dir = "/tmp/bench_loader_tok"
        if not os.path.exists(os.path.join(root, "meta")):
            build_parquet_dataset(root)
            print("# built parquet text shards", file=sys.stderr)
        if not os.path.exists(os.path.join(tok_dir, "tokenizer.json")):
            build_tokenizer(tok_dir)
            print("# trained local BPE tokenizer", file=sys.stderr)
        extra = dict(
            file_type="hf_parquet",
            tokenizer_path=tok_dir,
            col_name="text",
            vocab_size=8192,
        )

    cfg = TrainConfig(
        data_path=root,
        datasets=extra.pop("datasets", "dataset_1"),
        weights=extra.pop("weights", "1"),
        seq_length=4096,
        batch_size=4,
        bos_token=None,
        eos_token=0,
        logical_shards=64,
        num_workers=num_workers,
        worker_mode=worker_mode,
        ckpt_load_path=os.path.join(root, "_no_ckpt"),
        resuming_dataset=False,
        **extra,
    )
    loader = get_data_loader(cfg, rank=0, world_size=1)
    it = iter(loader)

    for _ in range(10):  # warmup
        next(it)
    t0 = time.perf_counter()
    for _ in range(n_batches):
        next(it)
    dt = time.perf_counter() - t0
    tok_s = n_batches * cfg.batch_size * cfg.seq_length / dt
    if mode == "mixed":
        # per-corpus goodput: realized token shares from the live
        # mixing layer x pulled throughput
        from fms_fsdp_tpu.data import loader_mix_stats

        mix = loader_mix_stats(loader) or {"tokens": {}, "quarantined": []}
        total = sum(mix["tokens"].values()) or 1
        mix_extras = {
            "per_corpus_tokens_per_sec": {
                n: round(tok_s * t / total) for n, t in mix["tokens"].items()
            },
            "realized_shares": {
                n: round(t / total, 3) for n, t in mix["tokens"].items()
            },
        }
    if hasattr(loader, "shutdown"):
        loader.shutdown()
    return tok_s, mix_extras


def main():
    demand_194m = 65_000 * 8  # tok/s, 8-chip host at the 194m rate
    demand_7b = 30_000 * 8

    rows = []
    nw = int(os.environ.get("BENCH_WORKERS", "8"))
    plans = [
        ("arrow", 1, 200, "thread"),
        # weighted 3-corpus mixing over the same arrow path: the mix
        # overhead vs the flat corpus (SamplingDataset bookkeeping +
        # per-corpus reader churn) and per-corpus goodput become
        # regression-measurable
        ("mixed", 1, 200, "thread"),
        ("parquet", 1, 40, "thread"),
        # worker scaling, both parallelism models: threads lean on the
        # tokenizer's GIL-releasing rust encode; processes are the
        # reference's torch-DataLoader model, immune to GIL contention
        # in the pure-Python pipeline stages (needs a multi-CPU host to
        # show scaling — 1-CPU hosts measure contention, NOTES.md r3)
        ("parquet", nw, 40, "thread"),
        ("parquet", nw, 40, "process"),
    ]
    flat_arrow_tok_s = None
    for mode, workers, n_batches, wmode in plans:
        tok_s, mix_extras = run_mode(mode, workers, n_batches, wmode)
        row = {
            "pipeline": mode,
            "num_workers": workers,
            "worker_mode": wmode,
            "tokens_per_sec": round(tok_s),
            "vs_8chip_194m_demand": round(tok_s / demand_194m, 2),
            "vs_8chip_7b_demand": round(tok_s / demand_7b, 2),
        }
        if mode == "arrow":
            flat_arrow_tok_s = tok_s
        if mode == "mixed":
            row.update(mix_extras)
            if flat_arrow_tok_s:
                # < 1.0 = the mix costs throughput vs the flat corpus
                row["mix_vs_flat_corpus"] = round(tok_s / flat_arrow_tok_s, 2)
        rows.append(row)
        print(json.dumps(rows[-1]), file=sys.stderr)

    result = {
        "metric": "host dataloader throughput (1 process)",
        "host_cpus": os.cpu_count(),
        "rows": rows,
        # headline keeps the arrow production-path number
        "tokens_per_sec": rows[0]["tokens_per_sec"],
        "num_workers": rows[0]["num_workers"],
        "vs_8chip_194m_demand": rows[0]["vs_8chip_194m_demand"],
        "vs_8chip_7b_demand": rows[0]["vs_8chip_7b_demand"],
    }
    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_LOADER.json",
    )
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
