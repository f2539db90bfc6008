"""One adapter per kind of program: the only files of the benchmark that
import the system under test."""
