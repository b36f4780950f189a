"""Plain reference of the replicated log, independent of ``raft_tpu``.

It states what a committed run must have produced from the benchmark's
own inputs: the applied stream, every replica row's retained ring, and,
under Reed-Solomon erasure coding, the shard each row holds. Nothing
here imports the program or reads a table the program made.
"""
