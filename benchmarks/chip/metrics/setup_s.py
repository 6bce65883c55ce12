"""Seconds from process start to the window's start (the first due
request): loading, drawing the weights, compiling or loading every
program, and filling the prefix cache."""


def read(record):
    return record["setup_s"]
