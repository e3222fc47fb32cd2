"""Tokenizer tests.

Mirrors the reference's only executed tokenizer check — the round-trip
assert at GPT1.py:32 — and extends it: vocab properties, save/load, byte-BPE
training on the actual corpus.
"""

import numpy as np
import pytest

from replicatinggpt_tpu.tokenizers import (ByteBPETokenizer, CharTokenizer,
                                           get_tokenizer)


def test_char_roundtrip(corpus_text):
    tok = CharTokenizer.from_text(corpus_text)
    # Tiny Shakespeare char vocab is 65 (SURVEY.md §2.0, GPT1.py:57 intent)
    assert tok.vocab_size == 65
    s = "hello world\nFirst Citizen:"
    assert tok.decode(tok.encode(s)) == s


def test_char_save_load(tmp_path, corpus_text):
    tok = CharTokenizer.from_text(corpus_text)
    p = tmp_path / "char.json"
    tok.save(str(p))
    tok2 = CharTokenizer.load(str(p))
    assert tok2.encode("Romeo") == tok.encode("Romeo")


def test_bpe_train_roundtrip(tiny_corpus):
    tok = ByteBPETokenizer.train(tiny_corpus, vocab_size=512)
    assert tok.vocab_size == 512
    s = "First Citizen:\nBefore we proceed any further, hear me speak."
    ids = tok.encode(s)
    assert tok.decode(ids) == s
    # BPE must compress: fewer tokens than bytes
    assert len(ids) < len(s.encode("utf-8"))


def test_bpe_handles_unseen_text(tiny_corpus):
    tok = ByteBPETokenizer.train(tiny_corpus, vocab_size=300)
    s = "zyx 12345 éüß unseen!"
    assert tok.decode(tok.encode(s)) == s
    # ids past the vocabulary (a model vocab padded beyond the
    # tokenizer's; an untrained model samples them) decode to U+FFFD
    ids = tok.encode("ab")
    assert tok.decode(ids[:1] + [tok.vocab_size + 7] + ids[1:]) == "a\ufffdb"


def test_bpe_save_load(tmp_path, tiny_corpus):
    tok = ByteBPETokenizer.train(tiny_corpus, vocab_size=300)
    p = tmp_path / "bpe.json"
    tok.save(str(p))
    tok2 = ByteBPETokenizer.load(str(p))
    s = "Before we proceed"
    assert tok2.encode(s) == tok.encode(s)
    assert tok2.vocab_size == tok.vocab_size


def test_get_tokenizer_specs(tmp_path, tiny_corpus):
    assert get_tokenizer("char", tiny_corpus).kind == "char"
    tok = get_tokenizer("bpe", tiny_corpus, cache_dir=str(tmp_path))
    assert tok.kind == "bpe"
    # second call hits the cache file
    tok2 = get_tokenizer("bpe", tiny_corpus, cache_dir=str(tmp_path))
    assert tok2.encode("hear me") == tok.encode("hear me")
    with pytest.raises(ValueError):
        get_tokenizer("nope", tiny_corpus)


def test_o200k_preset_wiring():
    """The o200k-shakespeare preset carries the reference GPT1.py default
    tokenizer branch with the §8-B1 vocab bug FIXED: the configured vocab
    (200,064 = 128*1563, MXU lane-padded) covers o200k_base's ~200k ids
    instead of the reference's hard-coded 50257 (GPT1.py:29-36)."""
    from replicatinggpt_tpu.config import get_config
    cfg = get_config("o200k-shakespeare")
    assert cfg.tokenizer == "tiktoken:o200k_base"
    assert cfg.model.vocab_size == 200_064
    assert cfg.model.vocab_size % 128 == 0
    # char-GPT training hyperparams otherwise (the GPT1.py script)
    assert cfg.model.block_size == 256 and cfg.train.lr == 2e-4


def test_tiktoken_offline_error_is_actionable():
    """Without cached BPE ranks or network, the tiktoken wrapper must
    fail with the clear actionable error, not a raw urllib trace; where
    ranks ARE cached it must report the true n_vocab (the §8-B1 fix)."""
    pytest.importorskip("tiktoken")
    try:
        tok = get_tokenizer("tiktoken:o200k_base")
    except RuntimeError as e:
        assert "tiktoken" in str(e) and "bpe" in str(e).lower()
    else:
        assert tok.vocab_size > 200_000  # o200k's real id space
        ids = tok.encode("hello world")
        assert tok.decode(ids) == "hello world"
