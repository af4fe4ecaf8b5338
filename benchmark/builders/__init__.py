"""One builder per kind of system under test: ``serve`` (DecodeEngine
under traffic) and ``train`` (Executor / ParallelExecutor steps). A
configuration file names its builder under "builder": {"kind": ...}."""
