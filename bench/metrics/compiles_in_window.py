"""Backend compiles during the measured window (the program's
``CompileWatchdog``); anything but 0 is work that belongs in set-up."""


def read(ctx):
    return ctx["run"]["compiles"]
