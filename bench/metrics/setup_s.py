"""setup_s: process start to the first due request (weights, compile or
cache load, warm-up). Host clock."""


def read(facts):
    return facts["setup_s"]
