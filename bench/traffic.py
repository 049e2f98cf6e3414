"""The one traffic generator: a closed loop of clients, read from a mix
file (``bench/traffic/<mix>.json``).

Each client sends its next request the moment its last one ends.  A
request is the shared prefix (if the mix has one) followed by a private
prompt, and asks for a fixed number of answer tokens.  Requests come in
rounds of one request per client: every round holds the mix's private
prompt lengths, repeated evenly, and answer lengths evenly spaced over
the mix's range, paired and dealt to the clients by one fixed schedule
stream.  The run's seed permutes the clients and draws every token id,
never a length: every seed asks for the same work, in another order, so
the loop passes through the same sequence of steps and admissions in
every run.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

# independent streams drawn from one seed
_DOC_STREAM, _TOKEN_STREAM, _CLIENT_STREAM = 1, 2, 4
# the length schedule's own seed, the same for every run and mix
_SCHEDULE_SEED = 0


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


class ClosedLoopTraffic:
    def __init__(self, mix: Dict[str, Any], seed: int, vocab_size: int):
        if mix.get("loop") != "closed" or mix.get("think_s", 0) != 0:
            raise ValueError("the generator drives closed loops with no "
                             "think time")
        self.clients = int(mix["clients"])
        self.vocab = int(vocab_size)
        self.prompt_lens = [int(n) for n in mix["private_prompt_tokens"]]
        if self.clients % len(self.prompt_lens):
            raise ValueError("clients must be a multiple of the number of "
                             "private prompt lengths")
        lo, hi = mix["answer_tokens"]["min"], mix["answer_tokens"]["max"]
        self.answer_lens = [int(round(lo + (hi - lo) * i
                                      / max(self.clients - 1, 1)))
                            for i in range(self.clients)]
        self.shared = _rng(seed, _DOC_STREAM).integers(
            0, self.vocab, int(mix["shared_prefix_tokens"])).tolist()
        self._sched = np.random.default_rng(_SCHEDULE_SEED)
        self._tokens = _rng(seed, _TOKEN_STREAM)
        self._column = _rng(seed, _CLIENT_STREAM).permutation(self.clients)
        self._rounds: List[List[Tuple[int, int]]] = []
        self._sent = [0] * self.clients

    def _round(self) -> List[Tuple[int, int]]:
        lens = self.prompt_lens * (self.clients // len(self.prompt_lens))
        lens = [lens[i] for i in self._sched.permutation(self.clients)]
        answers = [self.answer_lens[i]
                   for i in self._sched.permutation(self.clients)]
        return list(zip(lens, answers))

    def next(self, client: int) -> Tuple[List[int], int, int]:
        """The client's next request: ``(prompt, private_len, max_new)``.
        Its lengths depend only on the mix and on how many the client
        has sent; its token ids are drawn from the seed in the order
        requests are sent."""
        k = self._sent[client]
        self._sent[client] += 1
        while len(self._rounds) <= k:
            self._rounds.append(self._round())
        n, max_new = self._rounds[k][self._column[client]]
        private = self._tokens.integers(0, self.vocab, n).tolist()
        return self.shared + private, n, max_new

    def prime_prompts(self, chunk: int) -> List[List[int]]:
        """Prompts that put the shared prefix into the prefix cache, each
        ``chunk`` tokens longer than the last, so no single prefill spans
        the whole prefix (the engine unembeds every prompt position)."""
        if not self.shared:
            return []
        n = len(self.shared)
        chunk = chunk or n
        return [self.shared[:min(e, n)] for e in range(chunk, n + chunk,
                                                       chunk)]

    def worst_case_pages(self, page: int) -> int:
        """KV pages if every client held its longest request at once."""
        def pages(n):
            return -(-n // page)
        return pages(len(self.shared)) + self.clients * pages(
            max(self.prompt_lens) + max(self.answer_lens) + 1)
