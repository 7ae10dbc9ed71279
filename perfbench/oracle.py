"""Plain-Python word and artist count: the ``analyze`` job's reference.

Written from the reference's rules as ``SURVEY.md`` documents them
("Behavioral contracts", 1-3), with nothing imported from the program:

* the file is read with Python's ``csv`` module (the program has its own
  byte-level record reader in Python and in C++);
* a word is a run of ASCII letters, digits and apostrophes in the lyric's
  UTF-8 bytes, lower-cased, counted when it is at least 3 bytes long; every
  other byte, non-ASCII ones included, separates words;
* the artist is the first field with C white space trimmed; an empty artist
  counts as a song and not as an artist;
* both tables are written count-descending, ties in byte order, the key
  always quoted with ``"`` doubled, under the header ``word,count`` /
  ``artist,count``.
"""

from __future__ import annotations

import collections
import csv
import re
from typing import Dict, Tuple

_WORD = re.compile(rb"[0-9A-Za-z']+")
_C_SPACE = " \t\n\r\x0b\x0c"


def count_csv(path: str) -> Tuple[Dict[bytes, int], Dict[str, int], int]:
    """``(word counts, artist counts, songs)`` of the dataset at ``path``."""
    words: collections.Counter = collections.Counter()
    artists: collections.Counter = collections.Counter()
    songs = 0
    with open(path, newline="", encoding="utf-8") as fh:
        reader = csv.reader(fh)
        next(reader)
        for row in reader:
            if len(row) < 4:
                continue
            songs += 1
            artist = row[0].strip(_C_SPACE)
            if artist:
                artists[artist] += 1
            # everything after the third comma is the lyric, as in the source
            text = ",".join(row[3:]).encode("utf-8").lower()
            words.update(w for w in _WORD.findall(text) if len(w) >= 3)
    return words, artists, songs


def table_bytes(header: str, counts) -> bytes:
    """A count table as the job writes it."""
    def key_bytes(key):
        return key if isinstance(key, bytes) else key.encode("utf-8")

    ordered = sorted(
        ((key_bytes(k), n) for k, n in counts.items()),
        key=lambda kv: (-kv[1], kv[0]),
    )
    out = [header.encode("ascii") + b",count\n"]
    out.extend(
        b'"' + key.replace(b'"', b'""') + b'",' + str(n).encode("ascii") + b"\n"
        for key, n in ordered
    )
    return b"".join(out)


def expected_tables(path: str) -> Dict[str, bytes]:
    """The bytes ``word_counts.csv`` and ``top_artists.csv`` must hold."""
    words, artists, _ = count_csv(path)
    return {
        "word_counts.csv": table_bytes("word", words),
        "top_artists.csv": table_bytes("artist", artists),
    }
