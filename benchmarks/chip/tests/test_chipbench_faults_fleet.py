"""The fleet entry driven on the CPU at a tiny size with the chip's look
skipped: ``correct`` holds for the sound open and closed loops and fails
for an answer altered where it is produced and for the exchange between
stages left out."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_tiny import run_cell, tiny_root

SEED = 2 ** 35 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("chipbench"))


@pytest.mark.parametrize("cell,metrics", [
    ("resnet34-224.stream", {"frame_p50_ms", "setup_s"}),
    ("vgg16-224.pipe4", {"frames_per_s", "setup_s"})])
def test_sound_run_is_correct(root, capsys, cell, metrics):
    res = run_cell(root, cell, SEED, capsys)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == metrics
    assert res["checks"]["missing"]["value"] == 0


def test_answer_altered_where_produced(root, capsys, monkeypatch):
    from repro.pipeline.stage import StageExecutor
    orig = StageExecutor.__call__

    def altered(self, *a):
        return {k: (v.at[..., 0].add(jnp.max(jnp.abs(v)))
                    if v.shape[-1] == 1000 else v)
                for k, v in orig(self, *a).items()}
    monkeypatch.setattr(StageExecutor, "__call__", altered)
    res = run_cell(root, "resnet34-224.stream", SEED, capsys)
    assert res["correct"] is False


def test_exchange_between_stages_left_out(root, capsys, monkeypatch):
    from repro.dist.transport import Message, Transport
    orig = Transport.send

    def dropped(self, msg):
        if "->" in self.link and msg.kind == "frame":
            msg = Message(msg.kind, msg.fids,
                          {k: v if k == "__image__" else np.zeros_like(v)
                           for k, v in msg.tensors.items()}, msg.meta)
        return orig(self, msg)
    monkeypatch.setattr(Transport, "send", dropped)
    res = run_cell(root, "vgg16-224.pipe4", SEED, capsys)
    assert res["correct"] is False
    assert res["checks"]["logit_err"]["value"] > 0.1
