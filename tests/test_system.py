"""End-to-end behaviour tests: the paper's Table 1 example queries (Q1–Q6)
through the full parse→bind→optimize→execute pipeline, plus the real-JAX
executor path."""
import json

import numpy as np
import pytest

from repro.core.database import IPDB
from repro.relational.table import Table

MOVIES = [
    {"mid": 1, "title": "Titanic", "plot": "ship sinks romance tragedy",
     "lang": "English"},
    {"mid": 2, "title": "Alien", "plot": "violence horror in space",
     "lang": "English"},
    {"mid": 3, "title": "Amelie", "plot": "whimsical paris romance",
     "lang": "French"},
]
REVIEWS = [
    {"rid": 1, "mid": 1, "review": "loved it, fantastic"},
    {"rid": 2, "mid": 1, "review": "terrible and boring"},
    {"rid": 3, "mid": 2, "review": "scary, bad sleep"},
]
CAST = [
    {"mid": 1, "cname": "James Cameron", "role": "Director"},
    {"mid": 2, "cname": "Ridley Scott", "role": "Director"},
]


def _g(row, name, default=""):
    if name in row:
        return row[name]
    for k, v in row.items():
        if k.endswith("__" + name):
            return v
    return default


def movie_oracle(instruction, rows):
    out = []
    for r in rows:
        o = {}
        plot = str(_g(r, "plot"))
        o["genre"] = ("horror" if "horror" in plot else
                      "romance" if "romance" in plot else "drama")
        o["main_character"] = "protagonist"
        o["language"] = str(_g(r, "lang", "English"))
        o["negative"] = any(w in str(_g(r, "review"))
                            for w in ("terrible", "boring", "bad"))
        o["match"] = ("violence" in plot) == \
            ("violence" in str(_g(r, "description")))
        o["style"] = "sweeping epic"
        o["maturity_label"] = "R" if "violence" in plot else "PG"
        o["description"] = "desc"
        out.append(o)
    if "maturity" in instruction and not rows:
        return [{"maturity_label": l, "description": f"d{l}"}
                for l in ("G", "PG", "PG-13", "R")]
    return out


@pytest.fixture()
def db():
    d = IPDB()
    d.register_table("Movie", Table.from_rows(MOVIES))
    d.register_table("Review", Table.from_rows(REVIEWS))
    d.register_table("CastT", Table.from_rows(CAST))
    d.register_oracle("movies", movie_oracle)
    d.sql("CREATE LLM MODEL o4mini PATH 'oracle:movies' ON PROMPT "
          "API 'https://api.openai.com/v1/'")
    return d


def test_q1_table_inference_projection(db):
    r = db.sql("SELECT title, genre, main_character FROM LLM o4mini (PROMPT "
               "'extract the {genre VARCHAR} and {main_character VARCHAR} "
               "from the {{plot}}', Movie)")
    assert r.table.column_names == ["title", "genre", "main_character"]
    byt = {x["title"]: x["genre"] for x in r.table.rows()}
    assert byt == {"Titanic": "romance", "Alien": "horror",
                   "Amelie": "romance"}


def test_q2_scalar_projection(db):
    r = db.sql("SELECT title, LLM o4mini (PROMPT 'what is the "
               "{language VARCHAR} of the movie {{title}}') AS language "
               "FROM Movie")
    assert len(r.table) == 3
    assert "language" in r.table.column_names


def test_q3_table_generation(db):
    r = db.sql("CREATE TABLE MaturityRating AS SELECT maturity_label, "
               "description FROM LLM o4mini (PROMPT 'Get all the maturity "
               "{maturity_label VARCHAR} and {description VARCHAR} in US')")
    assert len(r.table) == 4
    assert db.catalog.has_table("MaturityRating")


def test_q4_semantic_select_with_join(db):
    r = db.sql("SELECT review FROM Movie AS m NATURAL JOIN Review AS r WHERE "
               "LLM o4mini (PROMPT 'is the sentiment of the {{review}} "
               "{negative BOOLEAN}?') = TRUE AND title = 'Titanic'")
    assert [x["review"] for x in r.table.rows()] == ["terrible and boring"]


def test_q5_semantic_join(db):
    db.sql("CREATE TABLE MR AS SELECT maturity_label, description FROM "
           "LLM o4mini (PROMPT 'Get all the maturity {maturity_label VARCHAR} "
           "and {description VARCHAR} in US')")
    r = db.sql("SELECT title, maturity_label FROM Movie AS m JOIN MR AS mr "
               "ON LLM o4mini (PROMPT 'is maturity rating "
               "{{mr.description}} depicted in the {{m.plot}}')")
    assert len(r.table) >= 1


def test_q6_semantic_aggregate(db):
    r = db.sql("SELECT cname, LLM AGG o4mini (PROMPT 'Summarize the "
               "cinematography {style VARCHAR} by the {{plot}}s') AS style "
               "FROM CastT AS c NATURAL JOIN Movie AS m "
               "WHERE role = 'Director' GROUP BY cname")
    assert len(r.table) == 2
    assert all(x["style"] == "sweeping epic" for x in r.table.rows())


def test_real_jax_executor_end_to_end():
    """PREDICT through a real (random-weight) JAX model: structure is
    guaranteed by the grammar even though answers are noise."""
    d = IPDB()
    d.register_table("Items", Table.from_rows(
        [{"name": f"item{i}"} for i in range(3)]))
    d.sql("CREATE LLM MODEL tiny PATH 'jax:olmo-1b' ON PROMPT "
          "OPTIONS { 'batch_size': 2, 'max_str': 6 }")
    r = d.sql("SELECT name, LLM tiny (PROMPT 'guess the {color VARCHAR} "
              "of {{name}}') AS color FROM Items")
    assert len(r.table) == 3
    assert all(isinstance(c, str) for c in r.table.column("color"))
    assert r.stats.llm_calls == 2          # ceil(3 unique / batch 2)


def test_jax_model_options_pick_the_published_config(monkeypatch):
    """OPTIONS {'smoke': 0} serves the arch's published widths over its
    full vocabulary; smoke and seed are part of the engine cache key."""
    import repro.configs as C
    import repro.serving.engine as E
    built = []

    class Recorder:
        def __init__(self, cfg, **kw):
            built.append((cfg, kw))

    monkeypatch.setattr(E, "InferenceEngine", Recorder)
    d = IPDB()
    d.sql("CREATE LLM MODEL big PATH 'jax:olmo-1b' ON PROMPT "
          "OPTIONS { 'smoke': 0, 'seed': 3 }")
    d.sql("CREATE LLM MODEL tiny PATH 'jax:olmo-1b' ON PROMPT")
    for name in ("big", "tiny", "big"):
        d._make_executor(d.catalog.model(name))
    assert len(built) == 2                 # the second "big" reuses its engine
    (big, kw_big), (tiny, kw_tiny) = built
    assert big == C.get_config("olmo-1b") and big.vocab_size == 50304
    assert (kw_big["seed"], kw_tiny["seed"]) == (3, 0)
    assert tiny == C.get_smoke_config("olmo-1b").replace(vocab_size=259)


def test_tabular_model_full_path():
    """CREATE TABULAR MODEL → TabularExecutor (hubert-style classifier)."""
    d = IPDB()
    d.register_table("Clips", Table.from_rows(
        [{"cid": i, "loudness": float(i)} for i in range(4)]))
    d.register_tabular("cls", lambda rows: [
        {"category_id": int(r["loudness"] > 1.5)} for r in rows])
    d.sql("CREATE TABULAR MODEL categorizer PATH 'tabular:cls' "
          "ON TABLE Clips FEATURES (loudness) OUTPUT (category_id INTEGER)")
    # table-bound model: PREDICT relation in FROM (paper Listing 4 usage)
    r2 = d.sql("SELECT category_id FROM PREDICT categorizer (Clips)")
    assert list(r2.table.column("category_id")) == [0, 0, 1, 1]
