"""The corpus generator: same seed, same bytes; another seed, other lyrics
under the same distribution.  And the oracle against the program's own
pure-Python ingest (the one ``chip_smoke.py``'s oracle uses)."""

import collections

import numpy as np
import pytest

import corpus
import oracle

PARAMS = {
    "songs": 400, "artists": 25, "vocabulary_words": 3000,
    "words_per_lyric_median": 200, "words_per_lyric_sigma": 0.55,
    "words_per_lyric_clip": [20, 1500], "word_zipf_s": 1.0,
    "songs_per_artist_zipf_s": 0.8, "quoted_phrase_share": 0.05,
}


@pytest.fixture(scope="module")
def rows():
    return corpus.generate_rows(PARAMS, seed=5)


def test_same_seed_same_bytes(rows):
    again = corpus.generate_rows(PARAMS, seed=5)
    assert corpus.rows_to_csv_bytes(again) == corpus.rows_to_csv_bytes(rows)


def test_another_seed_other_lyrics_same_distribution(rows):
    other = corpus.generate_rows(PARAMS, seed=6)
    assert [r[3] for r in other] != [r[3] for r in rows]

    def words(rs):
        return np.array([len(r[3].split()) for r in rs])

    a, b = words(rows), words(other)
    assert len(rows) == len(other) == PARAMS["songs"]
    assert np.median(a) == pytest.approx(200, rel=0.15)
    assert np.median(b) == pytest.approx(np.median(a), rel=0.15)
    assert 20 <= a.min() and a.max() <= 1500 + 10
    # the word list is the same for every seed
    assert corpus.build_vocabulary(500) == corpus.build_vocabulary(500)
    assert {r[0] for r in other} <= set(
        corpus._artist_names(25, corpus.build_vocabulary(3000)))


def test_zipf_ranks_follow_the_law():
    ranks = corpus._zipf_ranks(np.random.default_rng(0), 1000, 1.0, 400_000)
    counts = np.bincount(ranks, minlength=1000)
    assert ranks.min() == 0 and ranks.max() == 999
    # P(k) ~ ln((k+2)/(k+1)) / ln(1001)
    assert counts[0] / 400_000 == pytest.approx(np.log(2) / np.log(1001), rel=0.02)
    assert counts[9] / 400_000 == pytest.approx(np.log(11 / 10) / np.log(1001), rel=0.05)


def test_rows_survive_the_csv(rows, tmp_path):
    path = tmp_path / "songs.csv"
    path.write_bytes(corpus.rows_to_csv_bytes(rows))
    assert corpus.read_rows(str(path)) == rows
    assert any('"' in r[3] for r in rows) and any("\n" in r[3] for r in rows)


def test_oracle_agrees_with_the_programs_python_ingest(rows, tmp_path):
    from music_analyst_tpu.data.ingest import ingest_python

    data = corpus.rows_to_csv_bytes(rows)
    path = tmp_path / "songs.csv"
    path.write_bytes(data)
    words, artists, songs = oracle.count_csv(str(path))
    theirs = ingest_python(data)
    assert songs == theirs.song_count == PARAMS["songs"]
    their_words = collections.Counter(
        theirs.word_vocab.tokens[i] for i in theirs.word_ids.tolist())
    assert {k.decode(): v for k, v in words.items()} == dict(their_words)
    their_artists = collections.Counter(
        theirs.artist_vocab.tokens[i] for i in theirs.artist_ids.tolist()
        if i >= 0)
    assert dict(artists) == dict(their_artists)


def test_oracle_rules_on_a_hand_made_file(tmp_path):
    path = tmp_path / "t.csv"
    path.write_text(
        'artist,song,link,text\n'
        '"Sons, Daughters",A,/x,"Don\'t go, DON\'T  \nma coração it\'s ab"\n'
        ' Solo ,B,/y,go go gone\n'
        ',C,/z,gone\n', encoding="utf-8")
    words, artists, songs = oracle.count_csv(str(path))
    assert songs == 3
    assert dict(artists) == {"Sons, Daughters": 1, "Solo": 1}
    # "go", "ma", "o", "ab" are under 3 bytes; the accent splits "coração"
    assert dict(words) == {b"don't": 2, b"cora": 1, b"it's": 1, b"gone": 2}
    tables = oracle.expected_tables(str(path))
    assert tables["word_counts.csv"] == (
        b'word,count\n"don\'t",2\n"gone",2\n"cora",1\n"it\'s",1\n')
    assert tables["top_artists.csv"] == (
        b'artist,count\n"Solo",1\n"Sons, Daughters",1\n')
