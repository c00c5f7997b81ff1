"""How far the device plane's clock lies off the host plane's in this
profiler session, less the runtime's completion latency: the median over the
slice's dispatches of |the device plane's end of the dispatch's last module -
the host plane's start of its ``decode.copyout.*``| (the moment the blocking
read learned that the result was ready). Not a cost of the program: the
correction a reader applies to ``idle_launch_ms`` / ``idle_return_ms`` of the
same run, whose signed value and spread are on the run's earlier line
``{"phase": "ready"}`` (harness/ready.py). None on the parent of PR 53."""


from harness.ready import offset_ms, say


def read(o):
    say(o)
    return offset_ms(o)
