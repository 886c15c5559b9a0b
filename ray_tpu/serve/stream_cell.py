"""What one token stream costs the threads behind the engine's `put`.

Since ISSUE 35 each stage of a token's way from the engine thread to the
socket counts in place, around its own block, into its stream's `StreamCell`;
the engine thread adds up what its cells gained since its last `decode`
record and notes the sums there (`serve/llm_paged.py::PagedLLMEngine._stream_sums`), so
they carry `profiled` like everything else on the record. The stages that
know a stream only by its request (`openai_api.py::_stream_deltas`,
`api.py::_stream_response`) find the cell by the PR-16 request id
(`anatomy.rid_of(body)`), as `serve/anatomy.py` keys its ledgers.
"""

from __future__ import annotations

import operator
import weakref


class StreamCell:
    """A number a stage. Every field has one writer at a time (the thread
    that runs that stage), so a cell takes no lock and no stage waits for
    another; the engine thread only reads. Counts and `wake` are kept always;
    the `_cpu` fields (seconds of the stage's thread's CPU,
    `time.thread_time()` pairs) only while `timeline.profiling()`, because
    only profiled records are read for them.

    `put`: tokens the engine put on the stream's queue; `taken`: tokens the
    stream's thread took off it; `wake`: over those, seconds from the put to
    the `get`'s return; `detok_cpu`: the detokeniser; `relay_cpu`: the
    replica thread from a yielded chunk to its resumption (the store of the
    chunk); `fetch_cpu`: the proxy's pool thread fetching a chunk;
    `write_cpu`: the proxy's event loop serialising and writing a frame.
    `ended`: the engine's side of the stream is over. `sink`: 0 no front end
    in this process counts into the cell, 1 one does, 2 it is done; a cell is
    folded away when it has ended and no sink is open."""

    COUNTS = ("taken", "wake", "detok_cpu", "relay_cpu", "fetch_cpu", "write_cpu")
    __slots__ = COUNTS + ("put", "ended", "sink", "__weakref__")

    def __init__(self):
        self.put = self.taken = self.sink = 0
        self.wake = self.detok_cpu = self.relay_cpu = 0.0
        self.fetch_cpu = self.write_cpu = 0.0
        self.ended = False

    def counts(self) -> tuple:
        """The cell's numbers, in the order of `COUNTS`."""
        return _cell_counts(self)


_cell_counts = operator.attrgetter(*StreamCell.COUNTS)


# request id -> the cell of its stream: whichever stage asks first makes it.
# Weak: a cell lives as long as a stage or an engine holds it.
_cells: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


def stream_cell(rid: "str | None") -> StreamCell:
    """The cell of request `rid`'s stream in this process; a fresh one that
    nobody else will find for a request without an id."""
    cell = _cells.get(rid) if rid is not None else None
    if cell is None:
        cell = StreamCell()
        if rid is not None:
            cell = _cells.setdefault(rid, cell)
    return cell
