"""Subword tokenizers for the neural sentiment backends.

Replaces nothing in the reference: its LLM path sends raw text to an
Ollama server which tokenizes remotely (``scripts/sentiment_classifier.py:
85-100``); on-device models need explicit tokenizers.

This environment is zero-egress, so pretrained tokenizer assets may be
absent.  Three tiers, best available wins:

* a real WordPiece vocab (``vocab.txt``) or HF tokenizer directory supplied
  via path/env — exact DistilBERT tokenization;
* :class:`HashWordTokenizer` — deterministic hash of whitespace/punct-split
  words into the id space.  Calibration-free: architecture benchmarks and
  sharding tests don't depend on which subword each word maps to;
* :class:`ByteTokenizer` — raw UTF-8 bytes + specials, used by the decoder
  LM family offline.
"""

from __future__ import annotations

import os
import unicodedata
from typing import List, Optional, Sequence, Tuple

import numpy as np

_CJK_RANGES = (
    (0x4E00, 0x9FFF), (0x3400, 0x4DBF), (0x20000, 0x2A6DF),
    (0x2A700, 0x2B73F), (0x2B740, 0x2B81F), (0x2B820, 0x2CEAF),
    (0xF900, 0xFAFF), (0x2F800, 0x2FA1F),
)


def _is_bert_punctuation(ch: str) -> bool:
    """BERT treats the ASCII symbol ranges as punctuation in addition to
    the Unicode P* categories (so ``$``, ``+``, `` ` `` split too)."""
    cp = ord(ch)
    if (33 <= cp <= 47 or 58 <= cp <= 64 or 91 <= cp <= 96
            or 123 <= cp <= 126):
        return True
    return unicodedata.category(ch).startswith("P")


def bert_basic_tokenize(text: str) -> List[str]:
    """HF ``BertTokenizer``'s BasicTokenizer (``do_lower_case=True``),
    reimplemented exactly.

    Clean control chars (every C* category, like HF's ``_is_control``),
    isolate CJK ideographs, whitespace-split, lowercase + strip accents
    (NFD, drop combining marks), then split punctuation into single-char
    tokens.  The real-weights path depends on byte-exact agreement with
    the checkpoint's tokenizer — ``tests/test_wordpiece_differential.py``
    pins this function against ``transformers.BertTokenizer`` directly.
    """
    chars: List[str] = []
    for ch in text:
        cp = ord(ch)
        cat = unicodedata.category(ch)
        if ch in " \t\n\r" or cat == "Zs":
            chars.append(" ")
        elif cp == 0 or cp == 0xFFFD or cat.startswith("C"):
            continue
        elif any(lo <= cp <= hi for lo, hi in _CJK_RANGES):
            chars.extend((" ", ch, " "))
        else:
            chars.append(ch)
    tokens: List[str] = []
    for token in "".join(chars).split():
        token = token.lower()
        token = unicodedata.normalize("NFD", token)
        token = "".join(
            c for c in token if unicodedata.category(c) != "Mn"
        )
        current: List[str] = []
        for c in token:
            if _is_bert_punctuation(c):
                if current:
                    tokens.append("".join(current))
                    current = []
                tokens.append(c)
            else:
                current.append(c)
        if current:
            tokens.append("".join(current))
    return tokens


class HashWordTokenizer:
    """Deterministic word→id hashing into a fixed vocab space.

    Tokenization spec (deliberately byte-level so the native C++ fast path
    in ``native/ingest.cpp`` is exactly equivalent):

    * ASCII A-Z lowercases; words are runs of ``[a-z0-9']`` bytes;
    * ASCII whitespace separates; any other character — including each
      multi-byte UTF-8 character — is its own single-character token;
    * a word's id is ``reserved + FNV-1a(word bytes) % (vocab - reserved)``.
    """

    def __init__(
        self,
        vocab_size: int = 30522,
        cls_id: int = 101,
        sep_id: int = 102,
        pad_id: int = 0,
        reserved: int = 1000,
    ) -> None:
        if vocab_size < 16:
            raise ValueError("vocab_size too small for special tokens")
        self.vocab_size = vocab_size
        # keep specials + reserved range inside small vocabs
        self.cls_id = min(cls_id, vocab_size - 2)
        self.sep_id = min(sep_id, vocab_size - 1)
        self.pad_id = pad_id
        self.reserved = min(reserved, vocab_size // 2)

    def _hash_id(self, data: bytes) -> int:
        h = 2166136261
        for ch in data:
            h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
        return self.reserved + (h % (self.vocab_size - self.reserved))

    def _token_ids(self, text: str, max_tokens: int) -> List[int]:
        data = text.encode("utf-8", errors="replace")
        ids: List[int] = []
        i, n = 0, len(data)
        word_start = -1
        while i < n and len(ids) < max_tokens:
            b = data[i]
            if 65 <= b <= 90:
                b += 32  # ASCII lowercase
            is_word = (97 <= b <= 122) or (48 <= b <= 57) or b == 0x27
            if is_word:
                if word_start < 0:
                    word_start = i
                i += 1
                continue
            if word_start >= 0:
                ids.append(self._hash_id(data[word_start:i].lower()))
                word_start = -1
                if len(ids) >= max_tokens:
                    break
            if b in (0x20, 0x09, 0x0A, 0x0D, 0x0B, 0x0C):
                i += 1
                continue
            # single character token (UTF-8 multi-byte steps as one char)
            char_len = 1
            if b >= 0xF0:
                char_len = 4
            elif b >= 0xE0:
                char_len = 3
            elif b >= 0xC0:
                char_len = 2
            ids.append(self._hash_id(data[i : i + char_len]))
            i += char_len
        if word_start >= 0 and len(ids) < max_tokens:
            ids.append(self._hash_id(data[word_start:i].lower()))
        return ids

    def encode(self, text: str, max_len: int) -> Tuple[np.ndarray, int]:
        ids = [self.cls_id] + self._token_ids(text, max_len - 2) + [self.sep_id]
        length = len(ids)
        out = np.full(max_len, self.pad_id, dtype=np.int32)
        out[:length] = ids
        return out, length

    def encode_batch(
        self, texts: Sequence[str], max_len: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        batch = np.full((len(texts), max_len), self.pad_id, dtype=np.int32)
        lengths = np.zeros(len(texts), dtype=np.int32)
        for i, text in enumerate(texts):
            row, n = self.encode(text, max_len)
            batch[i] = row
            lengths[i] = n
        return batch, lengths


class NativeHashTokenizer(HashWordTokenizer):
    """C++-accelerated batch encoding with identical output."""

    def encode_batch(
        self, texts: Sequence[str], max_len: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        from music_analyst_tpu.data import native

        if not native.available():
            return super().encode_batch(texts, max_len)
        return native.hash_tokenize_batch(
            texts,
            max_len,
            vocab_size=self.vocab_size,
            cls_id=self.cls_id,
            sep_id=self.sep_id,
            pad_id=self.pad_id,
            reserved=self.reserved,
        )


class WordPieceTokenizer:
    """Greedy longest-match-first WordPiece over a provided ``vocab.txt``.

    Matches the BERT algorithm: basic whitespace+punctuation split,
    lowercase, then greedy subword segmentation with ``##`` continuations;
    unknown words map to ``[UNK]``.
    """

    SPECIAL_TOKENS = ("[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]")

    def __init__(self, vocab_path: str, max_word_chars: int = 100) -> None:
        import re

        with open(vocab_path, encoding="utf-8") as fh:
            self.vocab = {line.rstrip("\n"): i for i, line in enumerate(fh)}
        self.pad_id = self.vocab.get("[PAD]", 0)
        self.cls_id = self.vocab["[CLS]"]
        self.sep_id = self.vocab["[SEP]"]
        self.unk_id = self.vocab.get("[UNK]", 100)
        self.max_word_chars = max_word_chars
        self.vocab_size = len(self.vocab)
        # HF passes never_split=all_special_tokens to its basic tokenizer:
        # a literal "[MASK]" in the text stays one token (case-sensitive,
        # anywhere in the string), it is not lowercased or punct-split.
        self._specials = frozenset(
            t for t in self.SPECIAL_TOKENS if t in self.vocab
        )
        self._special_re = (
            re.compile("(" + "|".join(map(re.escape, self._specials)) + ")")
            if self._specials else None
        )

    def _wordpiece(self, word: str) -> List[int]:
        if len(word) > self.max_word_chars:
            return [self.unk_id]
        ids: List[int] = []
        start = 0
        while start < len(word):
            end = len(word)
            cur = None
            while start < end:
                piece = word[start:end]
                if start > 0:
                    piece = "##" + piece
                if piece in self.vocab:
                    cur = self.vocab[piece]
                    break
                end -= 1
            if cur is None:
                return [self.unk_id]
            ids.append(cur)
            start = end
        return ids

    def encode(self, text: str, max_len: int) -> Tuple[np.ndarray, int]:
        ids: List[int] = [self.cls_id]
        chunks = (
            self._special_re.split(text) if self._special_re else [text]
        )
        for chunk in chunks:
            if len(ids) >= max_len - 1:
                break
            if chunk in self._specials:
                ids.append(self.vocab[chunk])
                continue
            for word in bert_basic_tokenize(chunk):
                ids.extend(self._wordpiece(word))
                if len(ids) >= max_len - 1:
                    break
        ids = ids[: max_len - 1] + [self.sep_id]
        out = np.full(max_len, self.pad_id, dtype=np.int32)
        out[: len(ids)] = ids
        return out, len(ids)

    def encode_batch(
        self, texts: Sequence[str], max_len: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        batch = np.full((len(texts), max_len), self.pad_id, dtype=np.int32)
        lengths = np.zeros(len(texts), dtype=np.int32)
        for i, text in enumerate(texts):
            row, n = self.encode(text, max_len)
            batch[i] = row
            lengths[i] = n
        return batch, lengths


class ByteTokenizer:
    """UTF-8 bytes + specials: the offline tokenizer for the decoder LM."""

    PAD, BOS, EOS = 256, 257, 258

    def __init__(self, vocab_size: int = 512) -> None:
        assert vocab_size >= 259
        self.vocab_size = vocab_size
        self.pad_id = self.PAD
        self.bos_id = self.BOS
        self.eos_id = self.EOS

    def encode(self, text: str, max_len: int) -> Tuple[np.ndarray, int]:
        data = text.encode("utf-8")[: max_len - 1]
        ids = [self.BOS] + list(data)
        out = np.full(max_len, self.PAD, dtype=np.int32)
        out[: len(ids)] = ids
        return out, len(ids)

    def encode_batch(
        self, texts: Sequence[str], max_len: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        batch = np.full((len(texts), max_len), self.PAD, dtype=np.int32)
        lengths = np.zeros(len(texts), dtype=np.int32)
        for i, text in enumerate(texts):
            row, n = self.encode(text, max_len)
            batch[i] = row
            lengths[i] = n
        return batch, lengths

    def decode(self, ids: Sequence[int]) -> str:
        data = bytes(i for i in ids if 0 <= i < 256)
        return data.decode("utf-8", errors="replace")


class HFTokenizerAdapter:
    """Wrap a local HF tokenizer (e.g. Llama-3 BPE) behind the same
    ``encode``/``encode_batch``/``decode`` surface the offline tokenizers
    expose.  ``local_files_only`` — this environment has zero egress."""

    def __init__(self, path: str) -> None:
        from transformers import AutoTokenizer

        self.tok = AutoTokenizer.from_pretrained(path, local_files_only=True)
        self.vocab_size = len(self.tok)
        eos = self.tok.eos_token_id
        pad = self.tok.pad_token_id
        self.eos_id = eos if eos is not None else 0
        self.pad_id = pad if pad is not None else self.eos_id
        self.bos_id = self.tok.bos_token_id  # may be None (no-BOS styles)

    def encode(self, text: str, max_len: int) -> Tuple[np.ndarray, int]:
        ids = self.tok.encode(text, truncation=True, max_length=max_len)
        out = np.full(max_len, self.pad_id, dtype=np.int32)
        out[: len(ids)] = ids
        return out, len(ids)

    def encode_batch(
        self, texts: Sequence[str], max_len: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        # One batched call: fast tokenizers parallelize across texts here;
        # a per-text Python loop forfeits that on every 4k-song batch.
        # Padding happens in numpy so tokenizers without a pad token work.
        ids_list = self.tok(
            list(texts), truncation=True, max_length=max_len
        )["input_ids"]
        batch = np.full((len(texts), max_len), self.pad_id, dtype=np.int32)
        lengths = np.zeros(len(texts), dtype=np.int32)
        for i, ids in enumerate(ids_list):
            batch[i, : len(ids)] = ids
            lengths[i] = len(ids)
        return batch, lengths

    def decode(self, ids: Sequence[int]) -> str:
        return self.tok.decode(
            [int(i) for i in ids if int(i) != self.pad_id],
            skip_special_tokens=True,
        )


class HashWordLMTokenizer(HashWordTokenizer):
    """Offline decoder tokenizer over a large vocabulary: BOS, then
    :class:`HashWordTokenizer`'s word rule (about one token a word or
    mark), each word hashed over the whole id range.  The byte tokenizer
    would touch 259 rows of a 128k-row embedding and make every prompt
    four times a BPE's length.  Ids do not invert: ``decode`` names them.
    """

    # One token a word: the classifier closes a label continuation with
    # EOS so that it has a second token to score (models/llama.py).
    closes_labels = True

    def __init__(self, vocab_size: int) -> None:
        super().__init__(vocab_size, pad_id=0, reserved=16)
        self.bos_id, self.eos_id = 1, 2
        self._ids: dict = {}  # word bytes -> id (lyrics repeat their words)

    def _hash_id(self, data: bytes) -> int:
        found = self._ids.get(data)
        if found is None:
            found = self._ids[data] = super()._hash_id(data)
        return found

    def encode(self, text: str, max_len: int) -> Tuple[np.ndarray, int]:
        ids = [self.bos_id] + self._token_ids(text, max_len - 1)
        out = np.full(max_len, self.pad_id, dtype=np.int32)
        out[: len(ids)] = ids
        return out, len(ids)

    def decode(self, ids: Sequence[int]) -> str:
        return " ".join(f"<{int(i)}>" for i in ids
                        if int(i) not in (self.pad_id, self.bos_id,
                                          self.eos_id))


def resolve_llama_tokenizer(
    vocab_size: int, path: Optional[str] = None, kind: str = "byte"
):
    """Best-available decoder tokenizer.

    A local HF tokenizer directory (``$MUSICAAL_LLAMA_TOKENIZER``) gives
    exact BPE for real checkpoints; otherwise the offline tokenizer the
    configuration names (``kind``: ``"byte"`` or ``"hash_word"``) keeps
    everything runnable.
    """
    path = path or os.environ.get("MUSICAAL_LLAMA_TOKENIZER")
    if path and os.path.exists(path):
        return HFTokenizerAdapter(path)
    if kind == "hash_word":
        return HashWordLMTokenizer(vocab_size)
    return ByteTokenizer(vocab_size)


# Codepoints below this bound are classified/normalized by a table the
# Python side builds from unicodedata and hands to the native kernel:
# ASCII + Latin-1 Supplement + Latin Extended-A/B + IPA + combining
# diacriticals — i.e. every Western-language lyric.  Greek and beyond
# (0x370+) fall back to the Python path per row: lowercasing there can be
# context-dependent (final sigma), which a per-char table can't express.
_WP_TABLE_MAX = 0x370


def _wp_char_table():
    """``(classes, repl_blob, offsets)`` for the native WordPiece kernel.

    ``classes[cp]``: 0=drop (C* controls), 1=whitespace, 2=punctuation,
    3=word char.  ``repl`` is the per-char normalization BERT applies
    inside a token — lowercase, NFD, strip combining marks — as UTF-8
    bytes (empty for a bare combining mark, multi-byte where the
    lowercased base keeps a non-ASCII char like ``ø``).  Derived from the
    same unicodedata calls ``bert_basic_tokenize`` makes, so the native
    path can't drift from the Python semantics.
    """
    classes = np.zeros(_WP_TABLE_MAX, np.uint8)
    repls = []
    for cp in range(_WP_TABLE_MAX):
        ch = chr(cp)
        cat = unicodedata.category(ch)
        if ch in " \t\n\r" or cat == "Zs":
            classes[cp] = 1
            repls.append(b"")
        elif cp == 0 or cat.startswith("C"):
            classes[cp] = 0
            repls.append(b"")
        elif _is_bert_punctuation(ch):
            classes[cp] = 2
            repls.append(ch.encode("utf-8"))
        else:
            classes[cp] = 3
            norm = "".join(
                c for c in unicodedata.normalize("NFD", ch.lower())
                if unicodedata.category(c) != "Mn"
            )
            repls.append(norm.encode("utf-8"))
    offsets = np.zeros(_WP_TABLE_MAX + 1, np.int32)
    np.cumsum([len(r) for r in repls], out=offsets[1:])
    return classes, b"".join(repls), offsets


class NativeWordPieceTokenizer(WordPieceTokenizer):
    """C++-accelerated batch WordPiece with identical output.

    Latin-script rows (every Western-language lyric, accents included)
    encode in the threaded native kernel
    (``native/ingest.cpp:man_wp_encode_batch``) driven by the
    :func:`_wp_char_table` classification; rows the kernel flags
    (codepoints ≥ U+0370 or invalid UTF-8) re-encode through the Python
    path, which owns the full-Unicode BasicTokenizer semantics.  Python
    WordPiece runs ~10x slower than the DistilBERT device forward, so
    without this the real-weights path is tokenizer-bound.
    """

    def __init__(self, vocab_path: str, max_word_chars: int = 100) -> None:
        super().__init__(vocab_path, max_word_chars)
        from music_analyst_tpu.data import native

        self._native = native
        self._handle = (
            native.wp_create(vocab_path, _wp_char_table(), max_word_chars)
            if native.available() else None
        )

    def encode_batch(
        self, texts: Sequence[str], max_len: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        if self._handle is None:
            return super().encode_batch(texts, max_len)
        batch, lengths, handled = self._native.wp_encode_batch(
            self._handle, texts, max_len
        )
        for i in np.flatnonzero(handled == 0):
            row, n = self.encode(texts[i], max_len)
            batch[i] = row
            lengths[i] = n
        return batch, lengths

    def __del__(self):
        try:
            handle = getattr(self, "_handle", None)
            if handle:
                self._native.wp_destroy(handle)
        except Exception:
            # Interpreter teardown may have cleared module globals the
            # destroy path needs; leaking at exit beats a stderr
            # "Exception ignored" traceback in every process.
            pass


def resolve_bert_tokenizer(
    vocab_path: Optional[str] = None, vocab_size: int = 30522
):
    """Best-available encoder tokenizer (WordPiece if a vocab is supplied)."""
    path = vocab_path or os.environ.get("MUSICAAL_BERT_VOCAB")
    if path and os.path.exists(path):
        return NativeWordPieceTokenizer(path)
    return NativeHashTokenizer(vocab_size=vocab_size)
