"""LSTM text classification — the reference's RNN benchmark workload.

Reference: ``/root/reference/benchmark/paddle/rnn/rnn.py`` (embedding ->
2 x lstm -> fc over the last step; the published anchor is 184 ms/batch at
bs64 h512 seq100 vocab30k on 1xK40m, BASELINE.md). A library model, so
that whatever measures it measures the code users train.
"""

from __future__ import annotations

from ..core.module import Module
from .. import nn
from ..nn.recurrent import LSTMCell, RNN

__all__ = ["LSTMTextClassifier"]


class LSTMTextClassifier(Module):
    """``ids [B, T] -> logits [B, num_classes]`` via embedding -> stacked
    LSTMs -> fc on the final state."""

    def __init__(self, vocab: int, hidden: int = 512, num_layers: int = 2,
                 num_classes: int = 2, name=None):
        super().__init__(name=name)
        self.emb = nn.Embedding(vocab, hidden)
        # unroll measured NEUTRAL-to-worse under the bench's
        # steps-per-call fori_loop (XLA pipelines the rolled loop better);
        # see PERF.md (older installation) "Round 5"
        self.layers = [RNN(LSTMCell(hidden), name=f"lstm{i}")
                       for i in range(num_layers)]
        self.fc = nn.Linear(num_classes, name="fc")

    def forward(self, ids, train: bool = False):
        h = self.emb(ids)
        for layer in self.layers:
            h, _ = layer(h)
        return self.fc(h[:, -1])
