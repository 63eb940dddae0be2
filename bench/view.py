"""What a metric reader sees of one run: the window's requests and steps,
the encoder's calls, the program's spans, the reduced device trace, the
configuration and the device's peaks. Each helper returns what there is;
a reader returns None where there is nothing to read."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import flops
from loop import Window
from tokenizer import HashTokenizer


@dataclass
class Span:
    name: str
    start: float          # seconds after the window opened
    dur: float
    attrs: dict


class RunView:
    def __init__(self, cfg: dict, window: Window, *, setup_s: float,
                 encoder_calls: List[List[str]], spans: Optional[List[Span]] = None,
                 trace=None, peaks: Optional[dict] = None):
        self.cfg = cfg
        self.window = window
        self.setup_s = setup_s
        self.encoder_calls = encoder_calls
        self.spans = spans
        self.trace = trace
        self.peaks = peaks
        self._tok = HashTokenizer(cfg["vocab_size"])

    # -- the window --------------------------------------------------------
    @property
    def window_s(self) -> float:
        return self.window.close

    def requests(self, kind: str):
        return [r for r in self.window.requests if r.kind == kind]

    def latencies(self, kind: str) -> List[float]:
        """Seconds from due to done of every request of ``kind`` due in the
        window; one not done by the close counts at its age then."""
        close = self.window.close
        return [(r.done if r.done is not None else close) - r.due
                for r in self.requests(kind)]

    def token_gaps(self) -> List[float]:
        """Seconds between consecutive output tokens of every LM request;
        a request still decoding at the close adds its open gap."""
        out: List[float] = []
        for r in self.requests("lm"):
            ts = r.tokens
            out += [b - a for a, b in zip(ts, ts[1:])]
            if r.done is None and ts:
                out.append(self.window.close - ts[-1])
        return out

    def history_tokens(self) -> int:
        """Tokens of the sessions acknowledged in the window."""
        return sum(len(self._tok.encode(t.text))
                   for s in self.window.sessions for t in s.turns)

    def encoder_lengths(self) -> List[List[int]]:
        """Real tokens of each text, call by call, as the encoder truncates."""
        cap = self.cfg["serve"]["encoder_max_tokens"]
        return [[min(len(self._tok.encode(t)), cap) for t in texts]
                for texts in self.encoder_calls]

    # -- spans and trace ---------------------------------------------------
    def span_durations(self, name: str) -> Optional[List[float]]:
        if self.spans is None:
            return None
        return [s.dur for s in self.spans if s.name == name]

    def span_attr_sum(self, name: str, attr: str) -> Optional[int]:
        if self.spans is None:
            return None
        return sum(int(s.attrs.get(attr, 0)) for s in self.spans if s.name == name)

    # -- flops ----------------------------------------------------------------
    def heads(self) -> Dict[str, int]:
        c = self.cfg
        return {"heads": c["num_attention_heads"],
                "kv_heads": c["num_key_value_heads"],
                "head_dim": c["hidden_size"] // c["num_attention_heads"]}

    def peak_share(self, flops_: float) -> Optional[float]:
        """``flops_`` over the window at the chip's peak, in percent."""
        if self.peaks is None or self.window_s <= 0:
            return None
        return 100.0 * flops_ / (self.window_s * self.peaks["flops_bf16"])

    def roofline(self, kernel: str, calls) -> Optional[float]:
        """The least time of ``calls`` (an iterable of (flops, bytes) per
        kernel call) over the kernel's time in the trace, in percent."""
        from trace_reduce import kernel_s

        if self.trace is None or self.peaks is None:
            return None
        t = kernel_s(self.trace, kernel)
        if t <= 0:
            return None
        least = sum(flops.roofline_seconds(f, b, self.peaks)[0] for f, b in calls)
        return 100.0 * least / t
