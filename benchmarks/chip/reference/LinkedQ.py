"""Plain reference of LinkedQ, the first amendment's second design (Sela &
Petrank, SPAA'21, section 5.2), for one thread: a node is one persistent
line {item, next, initialized, pred}; an enqueue writes the content, then
the flag, links the node, flushes the suffix back to the first node known
durable (the new node and the durable tail) and fences once; a dequeue
clears the old head's flag and piggybacks the flush of the node it
retired before on its own single fence.

Departures from the paper: one thread, so no CAS ever fails and a thread's
``_to_flush`` slot is one field; no recovery, so the flag is written and
never read back.  Transcribed from ``repro.core.linked`` without the
schedule tables; it imports nothing of the system under test.
"""
from .memory import LINE_WORDS, NULL

ITEM, NEXT, INIT, PRED = 0, 1, 2, 3   # node layout: one persistent line


class Queue:
    def __init__(self, mem, alloc):
        self.mem, self.alloc = mem, alloc
        base = mem.alloc_region(2 * LINE_WORDS)
        self.HEAD, self.TAIL = base, base + LINE_WORDS
        self.persisted = set()          # nodes known durable (a hint)
        self.to_flush = NULL            # flag cleared, flush still owed
        dummy = alloc.alloc()
        mem.write_full_line(dummy, [None, NULL, 0, NULL, 0, 0, 0, 0])
        mem.write(self.HEAD, dummy)
        mem.write(self.TAIL, dummy)
        mem.pflush(dummy)
        mem.pflush(self.HEAD)
        mem.fence()
        self.persisted.add(dummy)

    def enqueue(self, item) -> None:
        mem = self.mem
        self.alloc.op_begin()
        node = self.alloc.alloc()
        self.persisted.discard(node)    # a reused address is not durable
        mem.write_full_line(node, [item, NULL, 0, NULL, 0, 0, 0, 0])
        while True:
            tail = mem.read(self.TAIL)
            if mem.read(tail + NEXT) == NULL:
                mem.write(node + PRED, tail)
                mem.write(node + INIT, 1)           # after the content
                if mem.cas(tail + NEXT, NULL, node):
                    walked = self.flush_walk(node)
                    mem.fence()                     # the one fence
                    self.persisted.update(walked)
                    mem.cas(self.TAIL, tail, node)
                    return
            else:
                mem.cas(self.TAIL, tail, mem.read(tail + NEXT))

    def flush_walk(self, node) -> list:
        """Flush back from the new node up to and including the first
        node known durable: its line holds the link onto the suffix."""
        mem, walked, p = self.mem, [], node
        while True:
            pred = mem.read(p + PRED)
            mem.pflush(p)
            walked.append(p)
            if p in self.persisted or pred == NULL:
                return walked
            p = pred

    def dequeue(self):
        mem = self.mem
        self.alloc.op_begin()
        while True:
            head = mem.read(self.HEAD)
            nxt = mem.read(head + NEXT)
            if nxt == NULL:
                mem.pflush(self.HEAD)
                mem.fence()
                return None
            tail = mem.read(self.TAIL)
            if head == tail:
                mem.cas(self.TAIL, tail, nxt)
                continue
            item = mem.read(nxt + ITEM)
            if mem.cas(self.HEAD, head, nxt):
                mem.write(head + INIT, 0)           # the old head's flag
                prev = self.to_flush
                if prev != NULL:
                    mem.pflush(prev)
                mem.pflush(self.HEAD)
                mem.fence()                         # the one fence
                if prev != NULL:
                    self.alloc.retire(prev)
                self.to_flush = head
                return item
