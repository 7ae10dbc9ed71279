"""The benchmark's corpus: a seeded stand-in for ``spotify_millsongdata.csv``.

The reference runs on the Kaggle "Spotify Million Song Dataset" file
(columns ``artist,song,link,text``; 57,650 songs, 643 artists).  The blob is
stripped from the reference repo, so the benchmark generates a corpus of the
same shape from ``--seed``.  What is taken from the source and what is
assumed is listed in the configuration file (``corpus`` and ``assumed``).

One general generator, parameters only from the configuration: the word
list is fixed (the same for every seed, so the distribution parameters are
the same); the lyrics, titles and the artist of each song are drawn from the
seed.  Same seed, same bytes.
"""

from __future__ import annotations

import csv
import io
import itertools
import json
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

# Bump when the generator's output for a given (params, seed) changes: the
# cached files under perfbench/out/ are keyed by it.
GENERATOR_VERSION = 1

_ONSETS = (
    "b c d f g h j k l m n p r s t v w y z bl br ch cl cr dr fl fr gl gr "
    "pl pr sh sl sm sn sp st sw th tr wh"
).split()
_VOWELS = "a e i o u ai ea ee ie oo ou".split()
_CODAS = ("", "", "", "n", "r", "s", "t", "l", "m", "d", "ng", "nd", "st", "ll")
_APOSTROPHE_ENDINGS = ("'s", "n't", "'re", "'m", "'ll", "in'", "'ve", "'d")
_ACCENTS = {"a": "á", "e": "é", "i": "í", "o": "ó", "u": "ú", "c": "ç", "n": "ñ"}
# Separators between words and how often each is drawn (assumed; the
# two-space line ending is how the source's lyric rows look).
_SEPARATORS = (
    (" ", 0.795), (", ", 0.045), ("  \n", 0.115), ("\n\n", 0.01),
    (". ", 0.012), ("! ", 0.006), ("? ", 0.006), (" (", 0.003), (") ", 0.003),
    ("-", 0.005),
)


def build_vocabulary(size: int, apostrophe_share: float = 0.08,
                     accent_share: float = 0.07) -> List[str]:
    """``size`` distinct pseudo-words, most frequent rank first.

    Fixed for every seed.  Frequent ranks are short (one syllable, many
    under three bytes, which the word count drops as real stop words are
    dropped), rare ranks long.  ``apostrophe_share`` of the words carry an
    apostrophe ending and ``accent_share`` one accented letter, spread
    over all ranks, the rates ``data/synthetic.py`` draws them at.
    """
    rng = np.random.default_rng(0xC0FFEE)
    words: List[str] = []
    seen = set()
    while len(words) < size:
        rank = len(words)
        if rank < 400:
            syllables = 1
        elif rank < 4000:
            syllables = 1 + int(rng.random() < 0.6)
        else:
            syllables = 2 + int(rng.random() < 0.45)
        parts = []
        for s in range(syllables):
            bare = s == 0 and rng.random() < (0.5 if rank < 40 else 0.1)
            onset = "" if bare else _ONSETS[rng.integers(len(_ONSETS))]
            parts.append(onset + _VOWELS[rng.integers(len(_VOWELS))])
        coda = "" if rank < 40 and rng.random() < 0.6 else (
            _CODAS[rng.integers(len(_CODAS))])
        word = "".join(parts) + coda
        kind = rng.random()
        if kind < apostrophe_share:
            word += _APOSTROPHE_ENDINGS[rng.integers(len(_APOSTROPHE_ENDINGS))]
        elif kind < apostrophe_share + accent_share:
            spots = [i for i, ch in enumerate(word) if ch in _ACCENTS]
            spot = spots[rng.integers(len(spots))]
            word = word[:spot] + _ACCENTS[word[spot]] + word[spot + 1:]
        if word not in seen:
            seen.add(word)
            words.append(word)
    return words


def _zipf_ranks(rng, n: int, s: float, size: int) -> np.ndarray:
    """``size`` ranks in ``[0, n)`` with P(k) proportional to the integral of
    ``x**-s`` over ``[k+1, k+2)``: Zipf's law drawn by inverting the
    continuous distribution, which costs one ``exp`` per draw instead of a
    search in a table of ``n`` entries."""
    u = rng.random(size, dtype=np.float32)  # float32: ranks stay far under 2**24
    if abs(s - 1.0) < 1e-9:
        x = np.exp(u * np.float32(np.log(n + 1.0)))
    else:
        top = np.float32((n + 1.0) ** (1.0 - s))
        x = (1 + u * (top - 1)) ** np.float32(1.0 / (1.0 - s))
    return np.clip(x.astype(np.int32) - 1, 0, n - 1)


def _artist_names(count: int, vocabulary: List[str]) -> List[str]:
    """``count`` distinct artist names (fixed for every seed), a few with
    commas, quotes and apostrophes so the CSV quoting is exercised."""
    rng = np.random.default_rng(0xA27157)
    pool = [w for w in vocabulary[len(vocabulary) // 300:len(vocabulary) // 10]
            if w.isascii() and w.isalpha()]
    names: List[str] = []
    seen = set()
    while len(names) < count:
        n_words = 1 + int(rng.integers(3))
        name = " ".join(
            pool[rng.integers(len(pool))].capitalize() for _ in range(n_words)
        )
        style = rng.random()
        if style < 0.15:
            name = "The " + name
        elif style < 0.19:
            name += ", " + pool[rng.integers(len(pool))].capitalize()
        elif style < 0.22:
            name = "O'" + name
        elif style < 0.24:
            name = f'{name} "{pool[rng.integers(len(pool))].capitalize()}"'
        if name not in seen:
            seen.add(name)
            names.append(name)
    return names


def generate_rows(params: Dict, seed: int) -> List[Tuple[str, str, str, str]]:
    """``(artist, song, link, text)`` rows for ``params`` and ``seed``."""
    songs = int(params["songs"])
    vocabulary = build_vocabulary(int(params["vocabulary_words"]))
    vocab_arr = np.array(vocabulary, dtype=object)
    rng = np.random.default_rng([GENERATOR_VERSION, int(seed)])

    lengths = rng.lognormal(
        np.log(params["words_per_lyric_median"]),
        params["words_per_lyric_sigma"], songs,
    )
    lo, hi = params["words_per_lyric_clip"]
    lengths = np.clip(lengths, lo, hi).astype(np.int64)
    total = int(lengths.sum())

    ranks = _zipf_ranks(rng, len(vocabulary), params["word_zipf_s"], total)
    sep_text = np.array([s for s, _ in _SEPARATORS], dtype=object)
    # a table of 1,000 slots, each separator holding its share of them
    table = np.repeat(
        np.arange(len(_SEPARATORS)),
        [int(round(p * 1000)) for _, p in _SEPARATORS],
    )
    seps = sep_text[table[rng.integers(0, len(table), total)]]
    pieces = np.empty(2 * total, dtype=object)
    pieces[0::2] = vocab_arr[ranks]
    pieces[1::2] = seps

    names = _artist_names(int(params["artists"]), vocabulary)
    artist_of = np.sort(_zipf_ranks(
        rng, len(names), params["songs_per_artist_zipf_s"], songs
    ))
    title_len = rng.integers(1, 6, songs)
    title_ranks = rng.integers(
        0, min(5000, len(vocabulary)), int(title_len.sum()))
    quoted = rng.random(songs) < params["quoted_phrase_share"]

    rows = []
    ends = np.cumsum(lengths) * 2
    t_end = np.cumsum(title_len)
    for i in range(songs):
        # the last separator of a lyric is dropped: rows end on a word
        text = "".join(pieces[ends[i] - 2 * lengths[i]: ends[i] - 1])
        if quoted[i]:
            text = f'She said "{text[:40]}" and {text[40:]}'
        title = " ".join(
            vocabulary[r].capitalize()
            for r in title_ranks[t_end[i] - title_len[i]: t_end[i]]
        )
        artist = names[artist_of[i]]
        link = "/{}/{}/{}_{}.html".format(
            artist[0].lower(), artist.lower().replace(" ", "+"),
            title.lower().replace(" ", "+"), 20000000 + i,
        )
        rows.append((artist, title, link, text))
    return rows


def rows_to_csv_bytes(rows) -> bytes:
    buf = io.StringIO(newline="")
    writer = csv.writer(buf, lineterminator="\n")
    writer.writerow(["artist", "song", "link", "text"])
    writer.writerows(rows)
    return buf.getvalue().encode("utf-8")


def corpus_dir(out_root: str, params: Dict, seed: int) -> str:
    return os.path.join(
        out_root, "corpus",
        f"v{GENERATOR_VERSION}-{params['songs']}songs-seed{int(seed)}",
    )


def ensure_corpus(out_root: str, params: Dict, seed: int) -> str:
    """Path of the corpus CSV for ``seed``, generated once per checkout."""
    directory = corpus_dir(out_root, params, seed)
    path = os.path.join(directory, "songs.csv")
    stamp = os.path.join(directory, "params.json")
    want = json.dumps(params, sort_keys=True)
    if os.path.exists(path) and os.path.exists(stamp):
        with open(stamp, encoding="utf-8") as fh:
            if fh.read() == want:
                return path
    os.makedirs(directory, exist_ok=True)
    data = rows_to_csv_bytes(generate_rows(params, seed))
    tmp = path + ".tmp"
    with open(tmp, "wb") as fh:
        fh.write(data)
    os.replace(tmp, path)
    with open(stamp, "w", encoding="utf-8") as fh:
        fh.write(want)
    return path


def read_rows(path: str, limit: Optional[int] = None,
              ) -> List[Tuple[str, str, str, str]]:
    """The corpus as the ``csv`` module parses it (artist, song, link, text),
    whole or its first ``limit`` rows."""
    with open(path, newline="", encoding="utf-8") as fh:
        reader = csv.reader(fh)
        next(reader)
        return [tuple(row) for row in itertools.islice(reader, limit)]
