"""eval_ppl entry coverage: the train -> checkpoint -> native-eval
leg. Validates
the params-only sharded load against a checkpoint the TRAINING ENTRY
actually wrote, and that a trained model scores better than random
init on the deterministic dummy stream."""

import os

import pytest

import main_training_llama
import eval_ppl

TINY = {
    "LlamaConfig.nlayers": 2,
    "LlamaConfig.emb_dim": 64,
    "LlamaConfig.nheads": 4,
    "LlamaConfig.kvheads": 2,
    "LlamaConfig.src_vocab_size": 256,
    "LlamaConfig.multiple_of": 16,
    "LlamaConfig.max_expected_seq_len": 64,
}

COMMON = dict(
    model_variant="llama2_7b",
    use_dummy_dataset=True,
    seq_length=64,
    vocab_size=256,
    batch_size=2,
    sharding_strategy="fsdp",
    attention_kernel="xla",
    **TINY,
)


def test_eval_ppl_from_entry_checkpoint(tmp_path, capsys):
    ckpt = str(tmp_path / "ckpt")
    main_training_llama.main(
        num_steps=30,
        report_interval=10,
        checkpoint_interval=30,
        ckpt_save_path=ckpt,
        ckpt_load_path=ckpt,
        **COMMON,
    )
    capsys.readouterr()

    trained = eval_ppl.main(
        ckpt_load_path=ckpt, eval_batches=4, **COMMON
    )
    assert trained["tokens"] > 0
    assert 0 < trained["ppl"] < 256  # better than uniform over the vocab

    # random init (fresh-init smoke mode, ckpt_load_path="") must score
    # clearly worse on the same stream — proves the checkpoint loaded.
    # (A nonexistent ckpt_load_path hard-fails by design.)
    fresh = eval_ppl.main(ckpt_load_path="", eval_batches=4, **COMMON)
    assert fresh["ppl"] > trained["ppl"] * 1.5, (fresh, trained)

    with pytest.raises(AssertionError, match="no checkpoint"):
        eval_ppl.main(
            ckpt_load_path=str(tmp_path / "nowhere"), eval_batches=1, **COMMON
        )
