"""CPU smoke of the train -> eval leg: a REAL arrow corpus through the
production data pipeline -> training entry -> native eval_ppl,
asserting perplexity actually falls vs the fresh-init model on the same
stream. This is the arrow-streaming -> training -> quality connection
at tiny scale."""

import pytest

import eval_ppl
import main_training_llama
from fms_fsdp_tpu.data.synth import build_arrow_corpus

TINY = {
    "LlamaConfig.nlayers": 2,
    "LlamaConfig.emb_dim": 64,
    "LlamaConfig.nheads": 4,
    "LlamaConfig.kvheads": 2,
    "LlamaConfig.src_vocab_size": 256,
    "LlamaConfig.multiple_of": 16,
    "LlamaConfig.max_expected_seq_len": 64,
}


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    return build_arrow_corpus(
        tmp_path_factory.mktemp("eval_data"), n_shards=2, docs_per_shard=80
    )


def test_eval_ppl_falls_after_training_on_arrow(corpus, tmp_path):
    data = dict(
        model_variant="llama2_7b",
        data_path=corpus,
        datasets="dataset_1",
        weights="1",
        file_type="arrow",
        vocab_size=256,
        logical_shards=8,
        seq_length=64,
        batch_size=2,
        sharding_strategy="fsdp",
        attention_kernel="xla",
        **TINY,
    )
    # explicit empty load path = fresh-init baseline (the TrainConfig
    # default points at /tmp/output/ckpt, which eval hard-fails on)
    fresh = eval_ppl.main(eval_batches=8, ckpt_load_path="", **data)
    assert fresh["tokens"] > 0

    ckpt = str(tmp_path / "ckpt")
    main_training_llama.main(
        num_steps=80,
        learning_rate=1e-3,
        report_interval=40,
        checkpoint_interval=80,
        ckpt_save_path=ckpt,
        ckpt_load_path=ckpt,
        **data,
    )

    trained = eval_ppl.main(eval_batches=8, ckpt_load_path=ckpt, **data)
    # the corpus is a 90%-deterministic counter chain: even 80 tiny
    # steps must beat the random-init model decisively
    assert trained["ppl"] < 0.9 * fresh["ppl"], (fresh, trained)
