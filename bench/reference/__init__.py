"""The plain reference the benchmark checks the program against.

It imports nothing of the program: the fabric, the job programs, the
placement draws and the network model are written out here in numpy
from the Union paper's description, and every input comes from the
benchmark's own configuration and traffic files and the member's seed.
"""
