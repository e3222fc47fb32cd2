"""Tokenizers: char-level, self-contained byte-level BPE, optional tiktoken.

Capability parity with the reference's tokenizer mux (GPT1.py:25-70):

- ``'base'`` char branch (GPT1.py:54-66)  -> :class:`CharTokenizer`
- ``'tiktoken'`` branch (GPT1.py:29-36)   -> :class:`TiktokenTokenizer`
  (optional: tiktoken fetches its BPE ranks over the network on first use,
  which is unavailable in air-gapped environments — so the framework also
  ships its own trainable byte-level BPE, :class:`ByteBPETokenizer`, giving
  the BPE capability with zero downloads)
- the broken ``'nltk'`` branch (GPT1.py:38-52, SURVEY.md §8-B2) is dropped
  deliberately.

All tokenizers expose the same interface the reference's encode/decode
closures had (GPT1.py:63-64): ``encode(str) -> list[int]``,
``decode(ids) -> str``, plus ``vocab_size`` and JSON save/load.
"""

from __future__ import annotations

import json
import os
from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

# GPT-2-style pre-tokenization pattern (public regex from the GPT-2 release;
# splits into contractions / letter runs / digit runs / symbol runs / spaces).
_PRETOKEN_PAT = (
    r"""'s|'t|'re|'ve|'m|'ll|'d| ?\p{L}+| ?\p{N}+| ?[^\s\p{L}\p{N}]+|\s+(?!\S)|\s+"""
)


def _bytes_to_unicode() -> Dict[int, str]:
    """Reversible byte <-> printable-unicode map (GPT-2's byte-level trick).

    Maps every possible byte to a unicode character that is printable and
    never a space, so BPE merges can be stored as plain strings.
    """
    bs = (list(range(ord("!"), ord("~") + 1))
          + list(range(ord("¡"), ord("¬") + 1))
          + list(range(ord("®"), ord("ÿ") + 1)))
    cs = bs[:]
    n = 0
    for b in range(256):
        if b not in bs:
            bs.append(b)
            cs.append(256 + n)
            n += 1
    return dict(zip(bs, [chr(c) for c in cs]))


_BYTE_ENCODER = _bytes_to_unicode()
_BYTE_DECODER = {v: k for k, v in _BYTE_ENCODER.items()}


class CharTokenizer:
    """Character-level tokenizer (GPT1.py:54-66 'base' branch).

    Vocabulary is the sorted set of characters of the corpus (65 for Tiny
    Shakespeare, verified in SURVEY.md §2.0).
    """

    kind = "char"

    def __init__(self, chars: Sequence[str]):
        self.chars = list(chars)
        self.stoi = {c: i for i, c in enumerate(self.chars)}
        self.itos = {i: c for i, c in enumerate(self.chars)}
        # byte->id LUT for the native fastpath; valid only for pure-ASCII
        # vocabularies (one utf-8 byte per char)
        self._lut = None
        if all(len(c) == 1 and ord(c) < 128 for c in self.chars):
            import numpy as np
            self._lut = np.full(256, -1, np.int32)
            for c, i in self.stoi.items():
                self._lut[ord(c)] = i

    @classmethod
    def from_text(cls, text: str) -> "CharTokenizer":
        return cls(sorted(set(text)))

    @property
    def vocab_size(self) -> int:
        return len(self.chars)

    def encode(self, s: str) -> List[int]:
        return [self.stoi[c] for c in s]

    def encode_np(self, s: str):
        """Corpus-scale encode via the native LUT kernel (identical ids)."""
        import numpy as np
        if self._lut is not None and len(s) > 4096:
            try:
                from .native import encode_lut
                return encode_lut(s.encode("utf-8"), self._lut)
            except ValueError:
                pass  # bytes outside alphabet: fall through for the KeyError
        return np.asarray(self.encode(s), np.int32)

    def decode(self, ids: Sequence[int]) -> str:
        return "".join(self.itos[int(i)] for i in ids)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"kind": self.kind, "chars": self.chars}, f)

    @classmethod
    def load(cls, path: str) -> "CharTokenizer":
        with open(path) as f:
            d = json.load(f)
        return cls(d["chars"])


class ByteBPETokenizer:
    """Self-contained byte-level BPE: trainable, saveable, download-free.

    Gives the framework the BPE capability of the reference's tiktoken branch
    (GPT1.py:29-36, GPT-2.py:192-196) without network access. Standard GPT-2
    construction: GPT-2 pre-tokenizer regex, byte-to-unicode base alphabet of
    256 symbols, then learned merges ranked by training order.
    """

    kind = "bpe"

    def __init__(self, merges: List[Tuple[str, str]],
                 vocab: Optional[List[str]] = None):
        import regex
        self._pat = regex.compile(_PRETOKEN_PAT)
        self.merges = [tuple(m) for m in merges]
        self.ranks = {m: i for i, m in enumerate(self.merges)}
        if vocab is None:
            base = [(_BYTE_ENCODER[b]) for b in range(256)]
            vocab = base + ["".join(m) for m in self.merges]
        self.vocab = vocab
        self.token_to_id = {t: i for i, t in enumerate(vocab)}
        self.id_to_token = {i: t for i, t in enumerate(vocab)}
        self._cache: Dict[str, List[int]] = {}
        self._ntable = False  # built lazily; None = native unusable

    # --- training ----------------------------------------------------------

    @classmethod
    def train(cls, text: str, vocab_size: int = 1024) -> "ByteBPETokenizer":
        """Learn merges on ``text`` until the vocab reaches ``vocab_size``.

        Counting is done on deduplicated pre-token "words" weighted by
        frequency, so training on megabyte-scale corpora is fast in pure
        Python.
        """
        import regex
        assert vocab_size > 256, "byte alphabet alone is 256 symbols"
        pat = regex.compile(_PRETOKEN_PAT)
        words = Counter()
        for w in pat.findall(text):
            units = tuple(_BYTE_ENCODER[b] for b in w.encode("utf-8"))
            words[units] += 1

        merges: List[Tuple[str, str]] = []
        words = dict(words)
        while 256 + len(merges) < vocab_size:
            pairs: Counter = Counter()
            for units, freq in words.items():
                for a, b in zip(units, units[1:]):
                    pairs[(a, b)] += freq
            if not pairs:
                break
            best = max(pairs, key=lambda p: (pairs[p], p))
            merges.append(best)
            merged = best[0] + best[1]
            new_words = {}
            for units, freq in words.items():
                out = []
                i = 0
                while i < len(units):
                    if (i + 1 < len(units)
                            and units[i] == best[0] and units[i + 1] == best[1]):
                        out.append(merged)
                        i += 2
                    else:
                        out.append(units[i])
                        i += 1
                new_words[tuple(out)] = new_words.get(tuple(out), 0) + freq
            words = new_words
        return cls(merges)

    # --- encode/decode -----------------------------------------------------

    def _bpe_word(self, word: str) -> List[int]:
        if word in self._cache:
            return self._cache[word]
        units = [_BYTE_ENCODER[b] for b in word.encode("utf-8")]
        while len(units) > 1:
            pairs = list(zip(units, units[1:]))
            ranked = [(self.ranks.get(p, 1 << 30), i) for i, p in enumerate(pairs)]
            rank, i = min(ranked)
            if rank >= (1 << 30):
                break
            units = units[:i] + [units[i] + units[i + 1]] + units[i + 2:]
        ids = [self.token_to_id[u] for u in units]
        self._cache[word] = ids
        return ids

    @property
    def vocab_size(self) -> int:
        return len(self.vocab)

    def encode(self, s: str) -> List[int]:
        out: List[int] = []
        for w in self._pat.findall(s):
            out.extend(self._bpe_word(w))
        return out

    def _native_merge_table(self):
        """Merge rules re-keyed into token-id space for the C++ kernel.

        Sound because id<->string is bijective over the ids the encoder can
        produce (token_to_id keeps the *last* id for duplicate merged
        strings — same dict semantics as ranks, tokenizers.py:111,116 — and
        base byte ids equal the raw byte value since base symbols are the
        only single-char vocab entries)."""
        if self._ntable is False:
            import numpy as np

            from .native import BpeMergeTable, available
            # the id-space kernel feeds raw utf-8 bytes as base token ids,
            # which is only sound when vocab slot b holds byte-symbol b for
            # all 256 base slots; a reordered/custom vocab (e.g. an edited
            # bpe_*.json) must fall back to the string-keyed Python path
            base_ok = all(
                self.token_to_id.get(_BYTE_ENCODER[b]) == b
                for b in range(256))
            if not available() or not base_ok:
                self._ntable = None
            else:
                pairs, rks, nids = [], [], []
                for (a, b), r in self.ranks.items():
                    merged = self.token_to_id.get(a + b)
                    ia, ib = self.token_to_id.get(a), self.token_to_id.get(b)
                    if merged is None or ia is None or ib is None:
                        continue  # unreachable rule (not in this vocab)
                    pairs.append((ia, ib))
                    rks.append(r)
                    nids.append(merged)
                self._ntable = BpeMergeTable(
                    np.asarray(pairs, np.int32).reshape(-1, 2),
                    np.asarray(rks, np.int32), np.asarray(nids, np.int32))
        return self._ntable

    def encode_np(self, s: str):
        """Corpus-scale encode via the native BPE kernel (identical ids)."""
        import numpy as np
        table = self._native_merge_table() if len(s) > 4096 else None
        if table is not None:
            from .native import bpe_encode_words
            bufs = [w.encode("utf-8") for w in self._pat.findall(s)]
            units = np.frombuffer(b"".join(bufs), np.uint8).astype(np.int32)
            off = np.zeros(len(bufs) + 1, np.int64)
            np.cumsum([len(b) for b in bufs], out=off[1:])
            out = bpe_encode_words(units, off, table)
            if out is not None:
                return out
        return np.asarray(self.encode(s), np.int32)

    def decode(self, ids: Sequence[int]) -> str:
        # an id past this vocabulary decodes to U+FFFD, like an
        # undecodable byte: a model's vocab may be padded beyond the
        # tokenizer's (gpt2-small's 50257 over the 1024-token corpus
        # BPE) and an untrained model samples from all of it — `cli
        # generate` from random init died here with a KeyError
        unknown = "\ufffd".encode("utf-8")
        data = b"".join(
            bytes(_BYTE_DECODER[ch] for ch in self.id_to_token[int(i)])
            if int(i) in self.id_to_token else unknown for i in ids)
        return data.decode("utf-8", errors="replace")

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"kind": self.kind, "merges": self.merges,
                       "vocab": self.vocab}, f)

    @classmethod
    def load(cls, path: str) -> "ByteBPETokenizer":
        with open(path) as f:
            d = json.load(f)
        return cls([tuple(m) for m in d["merges"]], d["vocab"])


class TiktokenTokenizer:
    """Wrapper over tiktoken encodings (GPT1.py:29-36 used o200k_base;
    GPT-2.py:192 used gpt2). Requires tiktoken's BPE ranks to be cached
    locally or downloadable; raises a clear error otherwise."""

    kind = "tiktoken"

    def __init__(self, encoding_name: str = "gpt2"):
        import tiktoken
        try:
            self.enc = tiktoken.get_encoding(encoding_name)
        except Exception as e:  # network failure in air-gapped envs
            raise RuntimeError(
                f"tiktoken encoding {encoding_name!r} unavailable (needs "
                f"cached BPE ranks or network). Use tokenizer='bpe' for the "
                f"self-contained byte-level BPE instead. Original: {e}"
            ) from e
        self.encoding_name = encoding_name

    @property
    def vocab_size(self) -> int:
        # Correct per-encoding vocab (fixes SURVEY.md §8-B1, where the
        # reference hard-coded 50257 for o200k_base).
        return self.enc.n_vocab

    def encode(self, s: str) -> List[int]:
        return self.enc.encode(s)

    def decode(self, ids: Sequence[int]) -> str:
        return self.enc.decode(list(int(i) for i in ids))


def get_tokenizer(spec: str, corpus_text: Optional[str] = None,
                  cache_dir: str = "datasets"):
    """Resolve a tokenizer spec string.

    - ``'char'``            : char vocab built from ``corpus_text``
    - ``'bpe'``             : byte-level BPE trained on ``corpus_text``
                              (cached to ``cache_dir/bpe_<vocab>.json``)
    - ``'bpe:<path>'``      : load a saved ByteBPETokenizer
    - ``'tiktoken:<name>'`` : tiktoken encoding (gpt2, o200k_base, ...)
    """
    if spec == "char":
        assert corpus_text is not None, "char tokenizer needs corpus text"
        return CharTokenizer.from_text(corpus_text)
    if spec == "bpe" or spec.startswith("bpe:"):
        if ":" in spec:
            return ByteBPETokenizer.load(spec.split(":", 1)[1])
        assert corpus_text is not None, "training BPE needs corpus text"
        cache = os.path.join(cache_dir, "bpe_1024.json")
        if os.path.exists(cache):
            return ByteBPETokenizer.load(cache)
        tok = ByteBPETokenizer.train(corpus_text, vocab_size=1024)
        try:
            os.makedirs(cache_dir, exist_ok=True)
            tok.save(cache)
        except OSError:
            pass
        return tok
    if spec.startswith("tiktoken"):
        name = spec.split(":", 1)[1] if ":" in spec else "gpt2"
        return TiktokenTokenizer(name)
    raise ValueError(f"unknown tokenizer spec {spec!r}")
